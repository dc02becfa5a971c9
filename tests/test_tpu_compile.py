"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: blocks that break the (8, 128) tiling rule, scratch
shapes Mosaic cannot lay out, more VMEM than a kernel may use.  Each test
here lowers one kernel at mesh-paper widths (d_model 2048, 16 heads of 128,
d_ff 8192) for one chip of a described `v5e:2x2` topology and checks that
the compiled program holds the Mosaic kernel (`tpu_custom_call`).  Nothing
runs; the kernels are called with `interpret=False` directly because the
planner, asked here, sees the CPU.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.trace_reduce import op_family  # noqa: E402
from repro.kernels.grouped import grouped_mesh_matmul_pallas  # noqa: E402
from repro.kernels.mesh_matmul import mesh_matmul_pallas, mesh_matmul_pallas_batched  # noqa: E402
from repro.kernels.paged_attention import paged_attention_pallas  # noqa: E402
from repro.kernels.scramble_kernel import scramble_blocks_pallas  # noqa: E402

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A described-chip compile written to the persistent cache cannot be
    # read back without the chip; keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str):
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the compiled program"


def test_mesh_matmul_fused_epilogue(one_chip):
    fn = functools.partial(
        lambda a, b, bias, res, **kw: mesh_matmul_pallas(
            a, b, bias=bias, residual=res, **kw
        ),
        block_m=256, block_n=512, block_k=256, activation="gelu",
    )
    hlo = _compile_hlo(
        fn, one_chip,
        ((2048, 2048), BF16), ((2048, 8192), BF16), ((8192,), BF16),
        ((2048, 8192), BF16),
    )
    _assert_kernel(hlo)


def test_mesh_matmul_scrambled(one_chip):
    fn = functools.partial(
        mesh_matmul_pallas, block_m=256, block_n=256, block_k=256, scramble_out=True
    )
    _assert_kernel(_compile_hlo(fn, one_chip, ((2048, 2048), BF16), ((2048, 2048), BF16)))


def test_mesh_matmul_batched(one_chip):
    fn = functools.partial(
        mesh_matmul_pallas_batched, block_m=256, block_n=256, block_k=512
    )
    _assert_kernel(
        _compile_hlo(fn, one_chip, ((4, 512, 2048), BF16), ((4, 2048, 512), BF16))
    )


@pytest.mark.parametrize(
    "m,k,n,blocks",
    [
        # forward (bm, bn, bk) = (256, 512, 128) on 2048x8192 @ 8192x2048:
        # dA = dz @ B^T takes (bm, bk, bn), dB = A^T @ dz takes (bk, bn, bm),
        # both on f32 operands (api._mm_bwd)
        (2048, 2048, 8192, (256, 128, 512)),
        (8192, 2048, 2048, (128, 512, 256)),
    ],
)
def test_mesh_matmul_backward_block_triples(one_chip, m, k, n, blocks):
    bm, bn, bk = blocks
    fn = functools.partial(
        mesh_matmul_pallas, block_m=bm, block_n=bn, block_k=bk, out_dtype=F32
    )
    _assert_kernel(_compile_hlo(fn, one_chip, ((m, k), F32), ((k, n), F32)))


def test_scramble_blocks(one_chip):
    fn = functools.partial(scramble_blocks_pallas, block_m=128, block_n=128, k=1)
    _assert_kernel(_compile_hlo(fn, one_chip, ((2048, 2048), BF16)))


def test_grouped_mesh_matmul(one_chip):
    fn = functools.partial(
        grouped_mesh_matmul_pallas, block_m=128, block_n=256, block_k=256
    )
    hlo = _compile_hlo(
        fn, one_chip, ((8 * 256, 2048), BF16), ((8,), I32), ((8, 2048, 1024), BF16)
    )
    _assert_kernel(hlo)


# (slots, heads, KV heads, page size, page slots, pool pages), hd 128
_PAGED_SHAPES = {
    # mesh-paper decode at pages of 8 and 16: a pool of 65 pages
    "8": (4, 16, 16, 8, 16, 65),
    "16": (4, 16, 16, 16, 16, 65),
    # the benchmark's serving cells: mesh-paper.chat, granite-3-8b.offline-long
    "chat": (64, 16, 16, 16, 64, 4097),
    "granite": (16, 32, 8, 16, 200, 3201),
}


@pytest.mark.parametrize("case", list(_PAGED_SHAPES))
def test_paged_attention(one_chip, case):
    slots, heads, kvh, page_size, n_pages, pool = _PAGED_SHAPES[case]
    hd = 128
    hlo = _compile_hlo(
        paged_attention_pallas, one_chip,
        ((slots, heads, hd), BF16),
        ((pool, kvh, page_size, hd), BF16),
        ((pool, kvh, page_size, hd), BF16),
        ((slots, n_pages), I32),
        ((slots,), I32),
    )
    _assert_kernel(hlo)


# The device trace names an op by its HLO instruction; the benchmark's
# readers match these families (bench/trace_reduce.op_family), so a rename
# (a `name=` on a pallas_call, another wrapper) must keep them.
_NAMED_KERNELS = {
    "mesh_matmul_pallas": (
        functools.partial(mesh_matmul_pallas, block_m=256, block_n=256, block_k=256),
        [((512, 512), BF16), ((512, 512), BF16)],
    ),
    "paged_attention_pallas": (
        paged_attention_pallas,
        [((4, 16, 128), BF16), ((65, 16, 16, 128), BF16), ((65, 16, 16, 128), BF16),
         ((4, 16), I32), ((4,), I32)],
    ),
    # σ scrambling of a batch of activations, as training calls it
    "vmap_jit_scramble_blocks_pallas__": (
        functools.partial(scramble_blocks_pallas, block_m=128, block_n=128, k=1),
        [((2, 512, 512), BF16)],
    ),
}


@pytest.mark.parametrize("family", sorted(_NAMED_KERNELS))
def test_kernel_keeps_the_name_the_trace_readers_match(one_chip, family):
    fn, shapes = _NAMED_KERNELS[family]
    hlo = _compile_hlo(fn, one_chip, *shapes)
    kernels = {
        op_family(line.strip().removeprefix("ROOT "))
        for line in hlo.splitlines()
        if "tpu_custom_call" in line
    }
    assert family in kernels, kernels
