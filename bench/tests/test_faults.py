"""Drive a whole run of each cell at a CPU size (2,000 nodes, jobs of 2
rounds) with the timed path broken underneath, and see ``correct`` come
out false; and true without a fault."""
import pytest

from bench import faults
from bench.tests.small import run_small, small_cell

CELLS = ("fedgat-pubmed-k8",)
SEED = 2**31 + 11
ROUNDS = 2


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out, lines = run_small(small_cell(name, rounds=ROUNDS), SEED)
    assert out["correct"], lines


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_caught(name, fault):
    with faults.planted(fault):
        out, lines = run_small(small_cell(name, rounds=ROUNDS), SEED)
    assert not out["correct"], lines
