import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import.

"""Exact roofline terms for the LM cells via the layer-marginal fit.

XLA's cost_analysis counts a lax.scan body ONCE, so the compile-proof
lowering (scan over 42-64 layers) undercounts FLOPs/bytes/collectives.
This probe lowers each LM cell UNROLLED (scan_layers=False, kv-block loops
unrolled, loss in one chunk) at n_layers = 2 and 4, and fits

    quantity(L) = base + marginal * L / <probe is exact: no loops left>

so  total(L_full) = base + marginal * L_full.  Probes use an even layer
count so alternating-window archs contribute one local + one global layer
per marginal pair. GNN / recsys / engine cells have no loops in their HLO —
their dry-run rows are already exact and are copied through.

  PYTHONPATH=src python -m repro.launch.roofline_fit [--multi-pod]
      [--arch gemma2-9b --shape train_4k]

Appends rows to results/roofline.jsonl.
"""
import argparse
import dataclasses
import json
import sys

import jax
import numpy as np

import repro.kernels.flash_attention.ops as attn_ops
from repro.config.registry import get_arch
from repro.config.base import MoEConfig, TransformerConfig, shapes_for_family
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import _lm_model_flops, all_cells, build_cell
from repro.runtime.roofline import (
    V5E, analyze, parse_collectives,
)
from repro.runtime.telemetry import clock

RESULTS = "/root/repo/results/roofline.jsonl"


def _probe_cfg(cfg, L):
    return dataclasses.replace(
        cfg, n_layers=L, scan_layers=False, loss_chunks=1,
    )


def _measure(arch, shape, mesh, cfg_override):
    """Lower+compile one probe; return (flops, bytes, coll_wire, counts)."""
    import repro.config.registry as registry

    name = cfg_override.name

    def fake_factory():
        return cfg_override

    # temporarily register the override under the arch name
    old = registry._REGISTRY.get(arch)
    registry._REGISTRY[arch] = fake_factory
    try:
        cell = build_cell(arch, shape, mesh)
    finally:
        if old is not None:
            registry._REGISTRY[arch] = old
    with mesh:
        lowered = jax.jit(
            cell.step_fn, out_shardings=cell.out_shardings,
            donate_argnums=cell.donate,
        ).lower(*cell.arg_specs)
        compiled = lowered.compile()
    n_chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    rep = analyze("probe", lowered, compiled, n_chips)
    return (rep.hlo_flops, rep.hlo_bytes, rep.collective.wire_bytes,
            rep.collective.counts, cell.model_flops)


def _lm_hbm_bytes(cfg, shape, n_chips):
    """Analytic HBM traffic per step, global bytes — the fusion-aware
    counterpart of cost_analysis's unfused 'bytes accessed' (which counts
    every VMEM-resident flash/MoE tile as HBM): params read for fwd + bwd
    recompute + optimizer read/write, activation carries saved + reloaded,
    KV cache traffic for decode. Formulas in EXPERIMENTS.md §Roofline."""
    pbytes = cfg.param_count() * 2                      # bf16
    opt = cfg.param_count() * 4 * 2 * 2                 # m,v f32 read+write
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        carries = tokens * cfg.d_model * 2 * cfg.n_layers * 2   # save + load
        streams = tokens * cfg.d_model * 2 * cfg.n_layers * 8   # per-layer io
        return 3 * pbytes + opt + carries + streams
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return pbytes + tokens * cfg.d_model * 2 * cfg.n_layers * 6
    # decode: read every (active) param + the whole KV cache once per token
    n_active = (cfg.active_param_count()
                if isinstance(cfg, MoEConfig) else cfg.param_count())
    cache = (cfg.n_layers * shape.global_batch * cfg.n_kv_heads
             * shape.seq_len * cfg.head_dim * 2 * 2)
    return n_active * 2 + cache


def fit_lm_cell(arch, shape_name, mesh, multi_pod, out_path):
    cfg = get_arch(arch)
    shape_obj = {s.name: s for s in shapes_for_family(cfg.family)}[shape_name]
    kind = {"train": "train", "prefill": "prefill"}.get(shape_obj.kind, "decode")
    model_flops_full = _lm_model_flops(cfg, shape_obj, kind)
    shape = shape_name
    L_full = cfg.n_layers
    attn_ops.UNROLL_KV_SCAN = True
    try:
        t0 = clock()
        f2 = _measure(arch, shape, mesh, _probe_cfg(cfg, 2))
        f4 = _measure(arch, shape, mesh, _probe_cfg(cfg, 4))
    finally:
        attn_ops.UNROLL_KV_SCAN = False

    def fit(a, b):
        marginal = (b - a) / 2.0
        base = a - 2.0 * marginal
        return base + marginal * L_full

    flops = fit(f2[0], f4[0])
    nbytes = fit(f2[1], f4[1])
    coll = fit(f2[2], f4[2])
    model_flops = model_flops_full

    n_chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    adj_bytes = _lm_hbm_bytes(cfg, shape_obj, n_chips)
    t_comp = flops / (n_chips * V5E.flops)
    t_mem_raw = nbytes / (n_chips * V5E.hbm_bw)
    t_mem = adj_bytes / (n_chips * V5E.hbm_bw)
    t_coll = coll / V5E.ici_bw
    bound = max(t_comp, t_mem, t_coll)
    row = {
        "name": f"{arch}/{shape}",
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "multi_pod": multi_pod,
        "chips": n_chips,
        "fitted": True,
        "hlo_gflops": round(flops / 1e9, 1),
        "hlo_gbytes_raw": round(nbytes / 1e9, 2),
        "adj_gbytes": round(adj_bytes / 1e9, 2),
        "coll_gbytes": round(coll / 1e9, 4),
        "model_gflops": round(model_flops / 1e9, 1),
        "t_compute_ms": round(t_comp * 1e3, 3),
        "t_memory_ms": round(t_mem * 1e3, 3),
        "t_memory_raw_ms": round(t_mem_raw * 1e3, 3),
        "t_collective_ms": round(t_coll * 1e3, 3),
        "bottleneck": max(
            {"compute": t_comp, "memory": t_mem, "collective": t_coll},
            key=lambda k: {"compute": t_comp, "memory": t_mem,
                           "collective": t_coll}[k]),
        "useful_ratio": round(model_flops / flops, 3) if flops else 0.0,
        "roofline_frac": round(
            (model_flops / (n_chips * V5E.flops)) / bound, 3) if bound else 0,
        "probe_s": round(clock() - t0, 1),
        "coll_counts_probe_L4": f4[3],
    }
    print(json.dumps(row), flush=True)
    with open(out_path, "a") as f:
        f.write(json.dumps(row) + "\n")
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    lm = [c for c in all_cells()
          if isinstance(get_arch(c[0]), TransformerConfig)]
    cells = [(args.arch, args.shape)] if args.arch else lm
    failures = []
    for arch, shape in cells:
        print(f"=== fit {arch}/{shape} ===", flush=True)
        try:
            fit_lm_cell(arch, shape, mesh, args.multi_pod, args.out)
        except Exception as e:
            import traceback
            traceback.print_exc()
            failures.append((arch, shape, repr(e)[:200]))
    if failures:
        print("FAILURES:", failures)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
