"""The control: the reference in the program's place, with its SSSP
bounds' Bellman-Ford accumulated in float32 (the precision below the
configuration's exact integers), or stopped at half the supersteps it needs.
Each comes out not correct where the exact reference comes out correct,
at a size a test run holds. ``bench/control.py`` makes the same reading
at a cell's own size on the chip."""
import pytest

from bench import run, spec
from bench.tests import tiny


@pytest.fixture
def root(tmp_path, monkeypatch):
    r = tiny.make_root(tmp_path / "checkout")
    tiny.use_root(monkeypatch, r, tmp_path)
    return r


def _records(cell_name, seed, n):
    cell = spec.load_cell(cell_name)
    cr = run.CellRun(cell, seed)
    recs = [cr.query(i) for i in range(n)]
    cr.close()
    return cell, cr.graph, recs


# float32 is exact below 2^24; the Kronecker graph's distances (weights up
# to 2^26) pass it.
CASES = [("kron.bracket", "float32"),
         ("kron.bracket", "half_steps"),
         ("kron.sssp2x", "float32"),
         ("kron.sssp2x", "half_steps")]


@pytest.mark.parametrize("cell,control", CASES)
def test_control_is_not_correct(root, cell, control):
    c, graph, recs = _records(cell, 2**31 + 7, 6)
    assert run.is_correct(run.check_answers(c, graph, 5, recs))
    numbers = run.check_answers(c, graph, 5, recs, control=control)
    assert not run.is_correct(numbers), numbers
