"""A four-chip data-parallel training run (the train-2k cell under a 4 x 1
`local-dp` mesh) with the gradient exchange left out comes out not correct,
on four virtual CPU devices in a child process."""

import json
import os
import subprocess
import sys
import textwrap

from bench.harness import ROOT

CHILD = textwrap.dedent(
    """
    import json, sys, time
    sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
    import jax
    from bench import train_cell
    from bench.tests.small import small_cell
    cell = small_cell("mesh-paper.train-2k")
    cell.chips = 4
    cell.mix["mesh"] = "local-dp"
    out = {}
    for fault in ("", "no_exchange"):
        r = train_cell.run(cell, 2**33 + 303, 0.5, False, jax.devices()[:4], time.monotonic(), fault=fault)
        out[fault or "sound"] = not r["problems"] and all(v["ok"] for v in r["compared"].values())
    print(json.dumps(out))
    """
)


def test_no_exchange_is_not_correct_on_four_devices():
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST")}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}
