"""A checkout in miniature for the CPU tests: ``BENCHMARK.json`` and data
files under a temporary root, with the real configurations cut to a few
hundred nodes, every real traffic mix, and every metric reader of
``bench/metrics/`` (a reader that finds nothing to read is left out of a
result line)."""
from __future__ import annotations

import glob
import json
import os
import shutil

from bench import spec

REAL = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
CONFIGS = {"kron": ("kron-g500.json", {"scale": 8})}
CELLS = ["kron.bracket", "kron.sssp2x"]
E2E = ("bracket_s", "bracket_ratio", "peak_hbm_bytes", "setup_s")


def make_root(path, extra_configs=(), extra_traffic=()) -> str:
    """Write a miniature checkout under ``path`` holding the cells
    ``CELLS``. ``extra_*`` are (name, dict) files to add besides."""
    os.makedirs(os.path.join(path, "bench", "configs"), exist_ok=True)
    shutil.copytree(os.path.join(spec.BENCH_DIR, "traffic"),
                    os.path.join(path, "bench", "traffic"),
                    dirs_exist_ok=True)
    configs = []
    for name, (file, sizes) in CONFIGS.items():
        cfg = spec.read_json(os.path.join(spec.BENCH_DIR, "configs", file))
        cfg.update(sizes, name=name)
        _write(os.path.join(path, "bench", "configs", name + ".json"), cfg)
        configs.append({"name": name, "source": cfg["source"][:200],
                        "file": f"bench/configs/{name}.json",
                        "reduced": sorted(sizes), "why": "test"})
    readers = sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(spec.BENCH_DIR, "metrics", "[!_]*.py")))
    bench = {
        **{k: REAL[k] for k in ("command", "paths", "run_seconds")},
        "configs": configs,
        "workloads": [{"name": c, "config": c.split(".")[0],
                       "traffic": c.split(".")[1], "chips": 1, "why": "test"}
                      for c in CELLS],
        "end_to_end": [{"name": m, "unit": "x", "better": "lower",
                        "bound": 0.25, "source": "host_clock"} for m in E2E],

        "per_layer": [{"name": m, "unit": "x", "better": "lower",
                       "source": "program_span", "layer": "test",
                       "moves": "bracket_s"}
                      for m in readers if m not in E2E],
    }
    # a 2-approximation's bracket is [ecc, 2 ecc]: its ratio says nothing
    bench["end_to_end"][1]["workloads"] = [c for c in CELLS
                                           if c.endswith(".bracket")]
    for name, cfg in extra_configs:
        _write(os.path.join(path, "bench", "configs", name + ".json"), cfg)
    for name, t in extra_traffic:
        _write(os.path.join(path, "bench", "traffic", name + ".json"), t)
    _write(os.path.join(path, "BENCHMARK.json"), bench)
    return str(path)


def _write(p, obj) -> None:
    with open(p, "w") as f:
        json.dump(obj, f, indent=1)


def use_root(monkeypatch, root, tmp_path) -> None:
    """Point the harness at ``root``, let it run on the CPU with the
    kernels interpreted, and keep its compile cache in ``tmp_path``."""
    import jax

    from bench import run

    monkeypatch.syspath_prepend(os.path.join(spec.ROOT, "src"))
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices())
    monkeypatch.setitem(run.ENGINE_OVERRIDES, "relax_impl", "interpret")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def run_cell(capsys, cell: str, seed: int = 2**31 + 12345,
             seconds: float = 0.5, trace: int = 0) -> dict:
    """``run.main`` on ``cell``; its last line of output as JSON."""
    from bench import run

    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
