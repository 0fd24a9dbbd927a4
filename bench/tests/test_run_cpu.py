"""``run.py`` end to end on the CPU at a tiny size, with the kernels
interpreted (steered from here: the script has no such option)."""
import json
import os
import subprocess
import sys

import pytest

from bench import spec
from bench.tests import tiny

# metrics each kind of mix reports at least (the CPU reports no memory
# peak and runs no edge-relax kernel in a trace)
WANT = {
    "bracket": ({"bracket_s", "bracket_ratio", "setup_s"},
                {"grow_s", "grow_supersteps", "quotient_s", "solve_supersteps",
                 "sssp_s", "sssp_superstep_ms", "host_syncs", "idle_share",
                 "compiles_in_window", "edge_relax_s",
                 "edge_relax_roofline"}),
    "sssp2x": ({"bracket_s", "setup_s"},
               {"sssp_s", "sssp_superstep_ms", "host_syncs", "idle_share",
                "compiles_in_window"}),
}


@pytest.fixture
def root(tmp_path, monkeypatch):
    r = tiny.make_root(tmp_path / "checkout")
    tiny.use_root(monkeypatch, r, tmp_path)
    return r


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_cell_runs_correct_with_its_end_to_end_metrics(root, capsys, cell):
    out = tiny.run_cell(capsys, cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == WANT[cell.split(".")[1]][0]
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["checked_queries"]["value"] >= 1
    assert all(c["limit"] == 0 for k, c in out["checks"].items()
               if k != "checked_queries")


def test_same_seed_same_answers(root, capsys):
    a = tiny.run_cell(capsys, "kron.sssp2x", seed=99)
    b = tiny.run_cell(capsys, "kron.sssp2x", seed=99)
    assert a["checks"] == b["checks"]


def test_a_new_config_and_mix_are_picked_up(tmp_path, monkeypatch, capsys):
    cfg = dict(spec.read_json(os.path.join(spec.ROOT, "bench", "configs",
                                           "kron-g500.json")))
    cfg.update(name="mini-kron", scale=6)
    mix = dict(spec.read_json(os.path.join(spec.BENCH_DIR, "traffic",
                                           "sssp2x.json")))
    mix["panel"] = [{"class": "LowerBoundEstimator", "args": {"rounds": 2}}]
    r = tiny.make_root(tmp_path / "checkout", [("mini-kron", cfg)],
                       [("walk2", mix)])
    bench = spec.read_json(os.path.join(r, "BENCHMARK.json"))
    bench["configs"].append({"name": "mini-kron", "source": "test",
                             "file": "bench/configs/mini-kron.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "mini-kron.walk2",
                               "config": "mini-kron", "traffic": "walk2",
                               "chips": 1, "why": "test"})
    tiny._write(os.path.join(r, "BENCHMARK.json"), bench)
    tiny.use_root(monkeypatch, r, tmp_path)
    out = tiny.run_cell(capsys, "mini-kron.walk2")
    assert out["correct"] is True
    assert "walk_lower_gap" in out["checks"]


def test_traced_run_reports_per_layer_metrics(root, capsys, monkeypatch):
    """The CPU's trace has no TPU plane, so the reduction reads the small
    trace recorded on the chip in its place, and the roofline takes the
    chip's peaks."""
    from bench import devtrace, peaks
    from bench.tests.test_devtrace import TRACE, load_recorded

    monkeypatch.setattr(devtrace, "load", lambda path: load_recorded())
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    out = tiny.run_cell(capsys, "kron.bracket", trace=1)
    assert out["correct"] is True
    assert set(out["metrics"]) == WANT["bracket"][1]
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    assert os.path.isfile(TRACE)


def test_exits_without_a_result_when_there_is_no_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "kron-g500.bracket", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=spec.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_unknown_cell_exits_without_a_result():
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "no-such.cell", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=spec.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.stdout or "")
