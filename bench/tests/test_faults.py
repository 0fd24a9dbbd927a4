"""A run with the timed path broken underneath comes out not correct, for
each fault a one-chip bracket or SSSP cell can have: a step that returns
its state unchanged, half of a batch left out, and an answer altered where
it is produced. (No cell exchanges data between chips.)"""
import numpy as np
import pytest

from bench.tests import tiny


@pytest.fixture
def root(tmp_path, monkeypatch):
    r = tiny.make_root(tmp_path / "checkout")
    tiny.use_root(monkeypatch, r, tmp_path)
    return r


def _unchanged_bf(monkeypatch):
    """Every Bellman-Ford superstep returns the distances it was given."""
    import repro.core.sssp as sssp

    monkeypatch.setattr(sssp, "_bf_loop",
                        lambda src, dst, w, d0, inf, n_nodes: (d0, 1))


def _half_batch(monkeypatch):
    """The quotient solve drops the second half of its sources."""
    import repro.core.estimators as est

    real = est.solve_device_quotient

    def half(dq, k, m, wmax=0):
        diam, ecc, connected, steps = real(dq, k, m, wmax)
        ecc = np.array(ecc)
        ecc[(k + 1) // 2:] = 0
        return int(ecc.max()), ecc, connected, steps

    monkeypatch.setattr(est, "solve_device_quotient", half)


def _altered_answer(monkeypatch):
    """One distance of every SSSP comes back one too long."""
    import repro.core.estimators as est

    real = est._sssp_from

    def altered(session, source, delta):
        dist, steps, inf = real(session, source, delta)
        dist = np.array(dist)
        dist[int(np.argmax(np.where(dist < inf, dist, -1)))] += 1
        return dist, steps, inf

    monkeypatch.setattr(est, "_sssp_from", altered)


FAULTS = {"unchanged_state": _unchanged_bf, "half_batch": _half_batch,
          "altered_answer": _altered_answer}
CASES = [("kron.bracket", f) for f in FAULTS] + [
    ("kron.sssp2x", "unchanged_state"),
    ("kron.sssp2x", "altered_answer")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_path_is_not_correct(root, capsys, monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    out = tiny.run_cell(capsys, cell)
    assert out["correct"] is False
    bad = {k for k, c in out["checks"].items()
           if c["limit"] is not None and c["value"] > c["limit"]}
    assert bad, out["checks"]
