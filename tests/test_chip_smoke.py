"""Rehearse `chip_smoke.py`'s phases on CPU at small widths.

The script itself refuses any platform but TPU; here its phase functions run
with Pallas in interpret mode (the paged kernel included), so a broken path,
argument or check shows up before a chip run.  The d_model 256 / seq 256
shape keeps the scrambling kernel on a square 2x2 block grid.
"""

import dataclasses
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import api  # noqa: E402
from repro.launch.mesh import forced_device_env  # noqa: E402
from repro.resilience import faults, ledger  # noqa: E402


def _small_cfg():
    return dataclasses.replace(
        get_config("mesh-paper").reduced(),
        d_model=256,
        head_dim=64,
        param_dtype="bfloat16",
        activation_dtype="bfloat16",
    )


@pytest.fixture()
def fresh(tmp_path, monkeypatch):
    for env in ("REPRO_AUTOTUNE_CACHE", "REPRO_COSTMODEL_CACHE"):
        monkeypatch.setenv(env, str(tmp_path / "unused"))
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    chip_smoke.prepare(tmp_path)
    api.clear_plan_cache()
    ledger.clear()
    yield tmp_path
    api.clear_plan_cache()
    ledger.clear()


def test_one_chip_phases_on_cpu(fresh):
    cfg = _small_cfg()
    tr = chip_smoke.train_phase(cfg, batch=2, seq=256, steps=2)
    assert len(tr["losses"]) == 2 and tr["tokens_per_step"] == 512
    sv = chip_smoke.serve_phase(
        cfg, n_requests=3, prompt_len=8, gen=4, slots=2, page_size=8, interpret=True
    )
    assert sv["paged_impl"] == "pallas_paged"
    assert sv["decode_tokens"] == 3 * 3  # the first token comes from prefill
    assert sv["paged_max_err"] <= sv["paged_tol"]
    assert chip_smoke.health_problems(sv["paged_impl"], interpret=True) == []


def test_health_problems_name_each_hidden_failure(fresh):
    a = jnp.ones((8, 8), jnp.float32)
    api.plan(api.GemmSpec.from_operands(a, a), backend="xla")
    ledger.record("autotune.measure", cause="test", fallback="skip-candidate")
    problems = chip_smoke.health_problems("xla_gather", interpret=True)
    assert len(problems) == 3
    assert "autotune.measure" in problems[0]
    assert "active backend xla" in problems[1]
    assert "xla_gather" in problems[2]


def test_main_refuses_a_platform_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_four_chip_phases_on_virtual_devices():
    prog = (
        "import dataclasses, tempfile; from pathlib import Path\n"
        "import chip_smoke as cs\n"
        "from repro.configs import get_config\n"
        "cs.prepare(Path(tempfile.mkdtemp()))\n"
        "rows = cs.sharded_phase(n=4, size=256)\n"
        "assert all(r['devices'] == [0, 1, 2, 3] for r in rows.values()), rows\n"
        "cfg = dataclasses.replace(get_config('mesh-paper').reduced(), d_model=256,"
        " head_dim=64)\n"
        "dp = cs.dp_train_phase(cfg, n=4, batch=4, seq=256, steps=2)\n"
        "assert dp['devices'] == [0, 1, 2, 3]\n"
        "assert cs.health_problems(None, interpret=True) == []\n"
        "print('FOUR_CHIP_OK', dp['loss_err'])\n"
    )
    env = forced_device_env(4, pythonpath=("src", "."))
    env.pop(faults.ENV_PLAN, None)
    out = subprocess.run(
        [sys.executable, "-c", prog], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_CHIP_OK" in out.stdout
