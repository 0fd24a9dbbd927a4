#!/usr/bin/env python3
"""One cold run of the diameter pipeline on a TPU, through the calls a user
makes (``open_session`` + estimators, what ``launch/diameter.py`` wraps).

  python chip_smoke.py             # one chip: phases A and B
  python chip_smoke.py --chips 4   # four chips: the sharded halo path only

Phase A (scale): a road-like graph of n = 2^18 nodes (~1.6M directed arcs;
the DIMACS 9th Implementation Challenge "NY" road network has 264,346 nodes)
runs ``ClusterQuotientEstimator`` with the session's default tau on the
compiled Pallas backend and again on the single-device backend: both must
give the same ``phi_approx`` (the cross-backend byte-identity contract), the
upper bound must reach the eccentricity scipy's Dijkstra finds from a seeded
source, and each query's measured device->host transfers must equal its
counted host syncs. Larger graphs do not fit this script's 20-minute budget
yet: on one TPU v5e, one relax superstep at n = 2^22 (25M arcs) took 1.25 s
on the pallas backend (1.13 s of it in the XLA source-plane gathers) and
3.3 s on the single-device backend, and a decomposition runs hundreds of
supersteps.

Phase B (exactness): ``IntervalEstimator`` on a road-like graph of n = 2,000
must bracket scipy's exact diameter.

``--chips 4`` runs the Phase A graph on the sharded backend (halo exchange
over four ``GraphStore`` shards) and on the one-chip pallas backend, and
requires the same ``phi_approx`` from both.

Exits non-zero, before any work and printing no result, unless JAX's first
device is a TPU. On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Every time printed is one cold run, compilation included.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_SCALE = 1 << 18
N_EXACT = 2000
SEED = 0


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _open(g, backend: str, relax_impl: str, store=None):
    """Open a session and wait for its resident edge arrays."""
    import jax

    from repro.config.base import GraphEngineConfig
    from repro.core import open_session

    cfg = GraphEngineConfig(backend=backend, relax_impl=relax_impl,
                            seed=SEED)
    t0 = time.perf_counter()
    sess = open_session(None if store is not None else g, cfg, store=store)
    jax.block_until_ready(sess.backend.graph_args())
    return sess, time.perf_counter() - t0


def _query(sess, label: str):
    """One cold ``ClusterQuotientEstimator`` query with every device->host
    transfer outside ``guard.fetch`` forbidden; returns the estimate."""
    from repro.analysis import guard
    from repro.core import ClusterQuotientEstimator

    t0 = time.perf_counter()
    with guard.measured_transfers() as meter:
        est = sess.estimate(ClusterQuotientEstimator())
    dt = time.perf_counter() - t0
    pm = est.pipeline
    _log(f"{label}: query_s={dt:.3f} phi_approx={est.phi_approx} "
         f"clusters={est.n_clusters} radius={est.radius} "
         f"stages={est.n_stages} grow_supersteps={est.growing_steps} "
         f"solve_supersteps={pm.solve_supersteps} "
         f"host_syncs={pm.total_host_syncs} "
         f"measured_transfers={meter.transfers}")
    _check(meter.transfers == pm.total_host_syncs,
           f"{label}: measured transfers {meter.transfers} != counted host "
           f"syncs {pm.total_host_syncs}")
    _check(est.connected, f"{label}: road-like graph reported disconnected")
    return est


def _pallas_session(g, relax_impl: str):
    sess, setup_s = _open(g, "pallas", relax_impl)
    want = "pallas" if relax_impl == "auto" else relax_impl
    _check(sess.backend.impl == want and sess.backend.fuse == 0,
           f"pallas backend runs impl={sess.backend.impl!r} "
           f"fuse={sess.backend.fuse}, want impl={want!r} fuse=0")
    return sess, setup_s


def phase_a(n: int = N_SCALE, relax_impl: str = "auto") -> dict:
    """Scale: pallas vs single on one road-like graph, against scipy."""
    import numpy as np
    from scipy.sparse.csgraph import dijkstra

    from repro.graph import road_like
    from repro.graph.structures import to_scipy_csr

    t0 = time.perf_counter()
    g = road_like(n, seed=SEED)
    _log(f"phase A: road_like n={g.n_nodes} arcs={g.n_edges} "
         f"build_s={time.perf_counter() - t0:.3f}")

    sess, setup_s = _pallas_session(g, relax_impl)
    _log(f"phase A pallas: setup_s={setup_s:.3f} impl={sess.backend.impl} "
         f"node_tile={sess.backend.node_tile} "
         f"edge_block={sess.backend.edge_block} "
         f"n_blocks={sess.backend.graph_args()[0].shape[0]}")
    pal = _query(sess, "phase A pallas")
    sess.close()

    sess, setup_s = _open(g, "single", relax_impl)
    _log(f"phase A single: setup_s={setup_s:.3f}")
    single = _query(sess, "phase A single")
    sess.close()
    _check(single.phi_approx == pal.phi_approx,
           f"phi_approx pallas {pal.phi_approx} != single "
           f"{single.phi_approx}")

    source = int(np.random.default_rng(SEED).integers(g.n_nodes))
    t0 = time.perf_counter()
    dist = dijkstra(to_scipy_csr(g), indices=source)
    ecc = int(dist[np.isfinite(dist)].max())
    _log(f"phase A reference: scipy dijkstra source={source} ecc={ecc} "
         f"upper={pal.upper} seconds={time.perf_counter() - t0:.3f}")
    _check(pal.upper is not None and pal.upper >= ecc,
           f"upper bound {pal.upper} below the eccentricity {ecc}")
    return {"phi_approx": pal.phi_approx, "ecc": ecc}


def phase_b(n: int = N_EXACT, relax_impl: str = "auto") -> dict:
    """Exactness: the certified bracket contains scipy's exact diameter."""
    import numpy as np
    from scipy.sparse.csgraph import shortest_path

    from repro.core import IntervalEstimator
    from repro.graph import road_like
    from repro.graph.structures import to_scipy_csr

    g = road_like(n, seed=SEED)
    sess, setup_s = _pallas_session(g, relax_impl)
    t0 = time.perf_counter()
    iv = sess.estimate(IntervalEstimator())
    query_s = time.perf_counter() - t0
    sess.close()
    d = shortest_path(to_scipy_csr(g), directed=False)
    exact = int(d[np.isfinite(d)].max())
    _log(f"phase B: n={n} setup_s={setup_s:.3f} query_s={query_s:.3f} "
         f"bracket=[{iv.lower}, {iv.upper}] exact={exact} "
         f"host_syncs={iv.pipeline.total_host_syncs}")
    _check(iv.lower <= exact <= iv.upper,
           f"bracket [{iv.lower}, {iv.upper}] misses the exact diameter "
           f"{exact}")
    return {"lower": iv.lower, "upper": iv.upper, "exact": exact}


def phase_sharded(n: int = N_SCALE, relax_impl: str = "auto") -> dict:
    """Sharded halo path over every device vs the one-chip pallas path."""
    import jax

    from repro.graph import GraphStore, road_like

    n_dev = len(jax.devices())
    t0 = time.perf_counter()
    g = road_like(n, seed=SEED)
    store = GraphStore(g, n_shards=n_dev)
    _log(f"sharded: road_like n={g.n_nodes} arcs={g.n_edges} "
         f"shards={n_dev} build_s={time.perf_counter() - t0:.3f}")

    sess, setup_s = _open(g, "sharded", relax_impl, store=store)
    eng = sess.backend.eng
    _check(eng.comm == "halo", f"sharded backend comm={eng.comm!r}")
    planes = sess.backend.init_state()  # what each decomposition starts from
    spans = {len(x.sharding.device_set) for x in (*planes, *eng.gparts)}
    _log(f"sharded: setup_s={setup_s:.3f} mesh={dict(eng.mesh.shape)} "
         f"plane/edge devices={sorted(spans)}")
    _check(spans == {n_dev},
           f"sharded planes/edges span {sorted(spans)} devices, "
           f"want {n_dev}")
    sh = _query(sess, "sharded halo")
    _log(f"sharded: halo_bytes={sh.pipeline.halo_bytes} "
         f"fullplane_bytes={sh.pipeline.fullplane_bytes}")
    sess.close()

    sess, setup_s = _pallas_session(g, relax_impl)
    _log(f"one-chip pallas: setup_s={setup_s:.3f}")
    pal = _query(sess, "one-chip pallas")
    sess.close()
    _check(sh.phi_approx == pal.phi_approx,
           f"phi_approx sharded {sh.phi_approx} != one-chip pallas "
           f"{pal.phi_approx}")
    return {"phi_approx": sh.phi_approx}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path over four chips and "
                         "the one-chip path it is compared with")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.common import enable_compile_cache

    _log(f"device: {dev.device_kind} x{len(devices)} "
         f"compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded()
    else:
        phase_a()
        phase_b()
    stats = dev.memory_stats() or {}
    _log(f"total_s={time.perf_counter() - t0:.3f} "
         f"peak_hbm_bytes={stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
