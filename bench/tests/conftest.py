"""Each benchmark run is a process of its own; a test that drives one starts
from the state such a process starts from, whatever other tests in this
worker left in the program's plan cache and degradation ledger (the health
checks read both)."""

import pytest


@pytest.fixture(autouse=True)
def fresh_program_state():
    from repro.kernels import api
    from repro.resilience import ledger

    api.clear_plan_cache()
    ledger.clear()
    yield
