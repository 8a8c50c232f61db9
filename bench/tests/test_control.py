"""The control, the reference computed one precision below the
configuration's (bfloat16 for float32) in the program's place, must come
out not correct against the cell's limits: here at a CPU size, over the
cell's own number of rounds."""
import pytest

from bench import compare, graphgen
from bench.readings import control_result
from bench.reference import federated
from bench.tests.small import small_cell


@pytest.mark.parametrize("name", ("fedgat-pubmed-k8",))
def test_control_is_not_correct(name):
    cell = small_cell(name)
    seed = 2**31 + 23
    rounds = int(cell.traffic["rounds"])
    arrays = graphgen.make_sbm(cell.config["graph"], seed)
    ref = federated.run(cell.config, cell.job, arrays, seed, rounds)
    nums = compare.numbers(control_result(cell, arrays, seed, rounds), ref, rounds)
    ok, lines = compare.judge(nums, cell.limits["limits"])
    assert not ok, lines
