"""Distributed-schedule benchmarks: Cannon/systolic phases on the ICI torus,
pipeline bubble fractions, and (in a 4-device subprocess) measured wall-time
of the overlapped ring collectives vs unfused all_gather+matmul.
"""

import os
import subprocess
import sys
import textwrap

from repro.launch.mesh import forced_device_env
from repro.parallel.pipeline import bubble_fraction
from repro.parallel.systolic import phase_counts


def run(csv=False):
    print("# distributed systolic matmul — collective phases (paper analogue)")
    print("p,chips,switched_phases,naive_phases,paper_mesh,paper_standard")
    for p in (2, 4, 8, 16, 32):
        pc = phase_counts(p)
        print(
            f"{p},{p*p},{pc['switched_phases']},{pc['naive_phases']},"
            f"{pc['paper_mesh_steps']},{pc['paper_standard_steps']}"
        )

    print("\n# GPipe bubble fraction (stages x microbatches)")
    print("stages,micro,bubble")
    for s in (2, 4, 8):
        for m in (4, 16, 64):
            print(f"{s},{m},{bubble_fraction(s, m):.4f}")

    print("\n# 4-device ring collective wall-time (subprocess, CPU devices)")
    prog = textwrap.dedent(
        """
        import time
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_local_mesh
        from repro.parallel.collectives import ring_allgather_matmul
        mesh = make_local_mesh((4,), ("model",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(1024, 512)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(512, 512)).astype(np.float32))
        ring = jax.jit(jax.shard_map(
            lambda xb, wb: ring_allgather_matmul(xb, wb, "model"),
            mesh=mesh, in_specs=(P("model", None), P()), out_specs=P(), check_vma=False))
        unfused = jax.jit(jax.shard_map(
            lambda xb, wb: jax.lax.all_gather(xb, "model", tiled=True) @ wb,
            mesh=mesh, in_specs=(P("model", None), P()), out_specs=P(), check_vma=False))
        for name, f in (("ring_overlapped", ring), ("allgather_then_matmul", unfused)):
            f(x, w).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(20):
                out = f(x, w)
            out.block_until_ready()
            print(f"{name},{(time.perf_counter()-t0)/20*1e3:.2f}ms")
        np.testing.assert_allclose(np.asarray(ring(x, w)), np.asarray(unfused(x, w)), rtol=1e-4, atol=1e-4)
        print("MATCH")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=forced_device_env(4),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=560,
    )
    if out.returncode != 0:
        raise RuntimeError(f"ring-collective subprocess failed: {out.stderr[-2000:]}")
    print(out.stdout.strip())
    return True


if __name__ == "__main__":
    run()
