"""A training run with the timed path broken underneath comes out not
correct: the harness's look for a chip is skipped, the rest of a run is
driven at a small size on the CPU, and the cell's own limits decide."""

import time

import jax
import pytest

from bench import train_cell
from bench.tests.small import small_cell

SEED = 2**33 + 101


def _run(name, fault=""):
    cell = small_cell(name)
    devices = jax.devices()[: cell.chips]
    out = train_cell.run(cell, SEED, 0.5, False, devices, time.monotonic(), fault=fault)
    return out["compared"], out["problems"]


def _correct(compared, problems):
    return not problems and all(v["ok"] for v in compared.values())


def test_sound_run_is_correct():
    compared, problems = _run("mesh-paper.train-2k")
    assert _correct(compared, problems), compared


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_fault_is_not_correct(fault):
    compared, problems = _run("mesh-paper.train-2k", fault)
    assert not _correct(compared, problems), compared


def test_control_in_the_programs_place_is_not_correct():
    """The reference computed with float8 matrix products, read against the
    float32 reference, fails the cell's limits."""
    cell = small_cell("mesh-paper.train-2k")
    from repro.models import get_model

    from bench import harness

    abstract = get_model(harness.arch_config(cell.config)).abstract_params()
    want = train_cell.reference_record(cell.config, cell.mix, abstract, SEED, 1)
    low = train_cell.reference_record(cell.config, cell.mix, abstract, SEED, 1, numerics="fp8")
    compared = harness.check(train_cell.readings(low, want), cell.limits)
    assert not all(v["ok"] for v in compared.values()), compared
