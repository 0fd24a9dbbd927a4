"""Tests for ``repro.analysis``: the static checkers (on a fixture corpus
of known-good / known-bad snippets, including regression snippets for the
PR 4 int32-overflow and PR 3 --tau-0 falsy-coercion bug classes) and the
runtime transfer-guard equality contracts
(``guard.measured_transfers() == the hand-incremented metrics``)."""
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import guard, run_analysis
from repro.analysis import (determinism_lint, dtype_lint, pallas_lint,
                            sync_lint)
from repro.analysis.common import SourceFile

REPO_SRC = str(Path(__file__).resolve().parents[1] / "src")


def lint(checker, text, path="snippet.py"):
    sf = SourceFile.parse(path=path, text=textwrap.dedent(text))
    return checker.check(sf)


def codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# sync-lint
# ---------------------------------------------------------------------------


class TestSyncLint:
    def test_int_of_device_value_flagged(self):
        fs = lint(sync_lint, """
            import jax.numpy as jnp

            def f(x):
                d = jnp.minimum(x, 0)
                return int(jnp.max(d))
        """)
        assert codes(fs) == ["SYNC001"]

    def test_item_and_tolist_flagged(self):
        fs = lint(sync_lint, """
            import jax.numpy as jnp

            def f(x):
                d = jnp.cumsum(x)
                a = d.item()
                b = d.tolist()
                return a, b
        """)
        assert codes(fs) == ["SYNC002"]
        assert len(fs) == 2

    def test_asarray_of_device_value_flagged(self):
        fs = lint(sync_lint, """
            import numpy as np
            import jax.numpy as jnp

            def f(x):
                d = jnp.sort(x)
                return np.asarray(d)
        """)
        assert codes(fs) == ["SYNC003"]

    def test_truthiness_of_device_value_flagged(self):
        fs = lint(sync_lint, """
            import jax.numpy as jnp

            def f(x):
                u = jnp.any(x)
                if u:
                    return 1
                return 0
        """)
        assert codes(fs) == ["SYNC004"]

    def test_iteration_over_device_value_flagged(self):
        fs = lint(sync_lint, """
            import jax.numpy as jnp

            def f(x):
                d = jnp.abs(x)
                return [v for v in d]
        """)
        assert codes(fs) == ["SYNC005"]

    def test_device_get_flagged(self):
        fs = lint(sync_lint, """
            import jax

            def f(x):
                return jax.device_get(x + 1)
        """)
        assert "SYNC006" in codes(fs)

    def test_jitted_params_are_tainted_except_static(self):
        fs = lint(sync_lint, """
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                return int(x) + int(n)
        """)
        # int(x) is one SYNC001; int(n) is static, hence host-side
        assert codes(fs) == ["SYNC001"]
        assert len(fs) == 1

    def test_guard_fetch_result_is_host_side(self):
        fs = lint(sync_lint, """
            import jax.numpy as jnp
            from repro.analysis import guard

            def f(x):
                stats = jnp.stack([x.sum(), x.max()])
                host = guard.fetch(stats, reason="test: packed stats")
                return int(host[0]), int(host[1])
        """)
        assert fs == []

    def test_metadata_and_none_checks_are_host_side(self):
        fs = lint(sync_lint, """
            import jax
            import jax.numpy as jnp

            def f(x, y):
                d = jnp.square(x)
                n = d.shape[0]
                if y is None and jax.default_backend() == "cpu":
                    return n
                return d.ndim
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# dtype-bound-lint
# ---------------------------------------------------------------------------


class TestDtypeLint:
    # the PR 4 overflow class, reduced to its shape
    PR4_BAD = """
        import jax.numpy as jnp

        def relax(src, w, n):
            d = jnp.full(n, 2**30, jnp.int32)
            return jnp.minimum(d, d[src] + w)
    """

    def test_pr4_int32_overflow_pattern_flagged(self):
        assert codes(lint(dtype_lint, self.PR4_BAD)) == ["DTYPE001"]

    def test_dtype_helper_clears_the_finding(self):
        fs = lint(dtype_lint, """
            import jax.numpy as jnp
            from repro.core.sssp import sssp_dtype_for

            def relax(src, w, n, wmax):
                dt = sssp_dtype_for(n, wmax, 0)
                d = jnp.full(n, 2**30, dt)
                return jnp.minimum(d, d[src] + w)
        """)
        assert fs == []

    # the PR 3 --tau 0 class: every falsy-coercion spelling
    @pytest.mark.parametrize("snippet", [
        "def f(tau):\n    return tau or 16\n",
        "def f(args):\n    return args.tau or 16\n",
        "def f(tau):\n    return not tau\n",
        "def f(levels):\n    if levels:\n        return 1\n    return 0\n",
    ])
    def test_pr3_falsy_knob_coercion_flagged(self, snippet):
        assert codes(lint(dtype_lint, snippet)) == ["DTYPE002"]

    def test_explicit_none_comparison_is_clean(self):
        fs = lint(dtype_lint, """
            def f(tau, levels):
                t = 16 if tau is None else tau
                if levels > 0:
                    t += levels
                return t
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# pallas-lint
# ---------------------------------------------------------------------------


class TestPallasLint:
    def test_index_map_arity_mismatch_flagged(self):
        fs = lint(pallas_lint, """
            from jax.experimental import pallas as pl

            def validate_tiling(nt, eb):
                return nt, eb

            def launch(kernel, x):
                validate_tiling(8, 128)
                return pl.pallas_call(
                    kernel,
                    grid=(4, 4),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                )(x)
        """)
        assert codes(fs) == ["PL001"]

    def test_vararg_index_map_satisfies_any_arity(self):
        fs = lint(pallas_lint, """
            from jax.experimental import pallas as pl

            def validate_tiling(nt, eb):
                return nt, eb

            def launch(kernel, x):
                validate_tiling(8, 128)
                return pl.pallas_call(
                    kernel,
                    grid=(4, 4),
                    in_specs=[pl.BlockSpec((8, 128), lambda i, *rest: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                )(x)
        """)
        assert fs == []

    def test_missing_validator_flagged(self):
        fs = lint(pallas_lint, """
            from jax.experimental import pallas as pl

            def launch(kernel, x):
                return pl.pallas_call(kernel, grid=(4,))(x)
        """)
        assert codes(fs) == ["PL002"]

    def test_oversized_scratch_flagged(self):
        fs = lint(pallas_lint, """
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def validate_tiling(nt, eb):
                return nt, eb

            def launch(kernel, x):
                validate_tiling(8, 128)
                return pl.pallas_call(
                    kernel,
                    grid=(4,),
                    scratch_shapes=[pltpu.VMEM((4096, 1024), jnp.float32)],
                )(x)
        """)
        # 4096*1024*4 = 16 MiB > the 8 MiB budget; the scratch+grid combo
        # without dimension_semantics also races (PL004)
        assert codes(fs) == ["PL003", "PL004"]

    def test_sequential_semantics_clear_the_race_finding(self):
        fs = lint(pallas_lint, """
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def validate_tiling(nt, eb):
                return nt, eb

            def launch(kernel, x):
                validate_tiling(8, 128)
                return pl.pallas_call(
                    kernel,
                    grid=(4,),
                    scratch_shapes=[pltpu.VMEM((8, 128), jnp.int32)],
                    compiler_params=pltpu.CompilerParams(
                        dimension_semantics=("arbitrary",)),
                )(x)
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# determinism-lint
# ---------------------------------------------------------------------------

DECOMP_PATH = "src/repro/core/engine.py"   # any decomposition-module path


class TestDeterminismLint:
    def test_global_rng_flagged_everywhere(self):
        fs = lint(determinism_lint, """
            import numpy as np

            def f():
                return np.random.rand(3)
        """, path="snippet.py")
        assert codes(fs) == ["DET001"]

    def test_seedless_default_rng_flagged_seeded_ok(self):
        bad = lint(determinism_lint, """
            import numpy as np

            def f():
                return np.random.default_rng()
        """)
        good = lint(determinism_lint, """
            import numpy as np

            def f(seed):
                return np.random.default_rng(seed)
        """)
        assert codes(bad) == ["DET001"] and good == []

    def test_wall_clock_flagged_in_clocked_scope(self):
        snippet = """
            import time

            def f():
                return time.perf_counter()
        """
        assert codes(lint(determinism_lint, snippet,
                          path=DECOMP_PATH)) == ["DET002"]
        # every repro module is in scope, not just the decomp set...
        assert codes(lint(determinism_lint, snippet,
                          path="src/repro/launch/serve.py")) == ["DET002"]
        # ...except the sanctioned clock seam itself and non-repro files
        assert lint(determinism_lint, snippet,
                    path="src/repro/runtime/telemetry.py") == []
        assert lint(determinism_lint, snippet, path="bench.py") == []

    def test_set_iteration_order_flagged_in_decomp_modules(self):
        fs = lint(determinism_lint, """
            import numpy as np

            def f(st):
                dirty = {1, 2, 3}
                a = list(dirty)
                b = np.fromiter(st.dirty_centers, np.int64)
                return a, b
        """, path=DECOMP_PATH)
        assert codes(fs) == ["DET003"] and len(fs) == 2

    def test_builtin_hash_flagged_in_decomp_modules(self):
        fs = lint(determinism_lint, """
            def f(name):
                return hash(name)
        """, path=DECOMP_PATH)
        assert codes(fs) == ["DET004"]


# ---------------------------------------------------------------------------
# pragma grammar (suppression + empty-reason errors), via run_analysis
# ---------------------------------------------------------------------------


class TestPragmas:
    def test_pragma_suppresses_but_is_reported(self, tmp_path):
        p = tmp_path / "annotated.py"
        p.write_text(textwrap.dedent("""
            import jax.numpy as jnp

            def f(x):
                d = jnp.cumsum(x)
                return d.item()  # sync: test corpus — intentional fetch
        """))
        active, suppressed, errors = run_analysis([str(p)])
        assert active == [] and errors == []
        assert codes(suppressed) == ["SYNC002"]

    def test_pragma_on_preceding_line_covers_statement(self, tmp_path):
        p = tmp_path / "annotated.py"
        p.write_text(textwrap.dedent("""
            import jax.numpy as jnp

            def f(x):
                d = jnp.cumsum(x)
                # sync: test corpus — pragma above the statement
                return d.item()
        """))
        active, suppressed, errors = run_analysis([str(p)])
        assert active == [] and errors == []
        assert codes(suppressed) == ["SYNC002"]

    def test_empty_reason_pragma_is_an_error(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("x = 1  # sync:\n")
        active, suppressed, errors = run_analysis([str(p)])
        assert codes(errors) == ["PRAGMA000"]

    def test_wrong_checker_pragma_does_not_suppress(self, tmp_path):
        p = tmp_path / "wrong.py"
        p.write_text(textwrap.dedent("""
            import jax.numpy as jnp

            def f(x):
                d = jnp.cumsum(x)
                return d.item()  # dtype: wrong pragma for a sync finding
        """))
        active, _, _ = run_analysis([str(p)])
        assert codes(active) == ["SYNC002"]


def test_repo_src_is_clean():
    """The acceptance contract: the full suite over src/ has zero active
    findings and zero errors (every intentional site is pragma-annotated)."""
    active, suppressed, errors = run_analysis([REPO_SRC])
    assert [f.format() for f in active] == []
    assert [f.format() for f in errors] == []
    assert suppressed   # the annotated fetch sites exist


# ---------------------------------------------------------------------------
# runtime transfer-guard equality contracts
# ---------------------------------------------------------------------------


def _graph():
    from repro.graph import random_geometric

    return random_geometric(512, avg_degree=6.0, seed=1)


class TestTransferGuardEquality:
    def test_stages_measured_equals_counted(self, transfer_guarded):
        from repro.core import cluster

        dec = cluster(_graph(), 12, seed=0)
        m = dec.metrics
        assert transfer_guarded.transfers == m.host_syncs + m.finalize_syncs
        # every transfer is a sanctioned, reasoned guard.fetch
        assert all(r for r in transfer_guarded.reasons())

    def test_oneshot_measured_equals_counted(self, transfer_guarded):
        from repro.core import cluster

        dec = cluster(_graph(), 12, seed=0, mode="oneshot")
        m = dec.metrics
        assert m.host_syncs == 1   # the mode's headline contract
        assert transfer_guarded.transfers == m.host_syncs + m.finalize_syncs

    def test_checkpointed_decomposition_measured_equals_counted(
            self, tmp_path, transfer_guarded):
        """The extended equality contract: with a StageCheckpointer armed,
        every device leaf the checkpoint writer materializes goes through
        guard.fetch and lands in ``checkpoint_syncs`` — so
        ``measured == host_syncs + finalize_syncs + checkpoint_syncs``
        and the durability cost never hides inside the algorithmic
        budget (``checkpoint_syncs`` stays OUT of total_host_syncs)."""
        from repro.core import StageCheckpointer, cluster

        # tau=4 keeps the stage threshold (8 tau log n) below n=512 so
        # the stage loop — and with it the boundary hook — actually runs
        ck = StageCheckpointer(str(tmp_path), every=1)
        dec = cluster(_graph(), 4, seed=0, checkpointer=ck)
        m = dec.metrics
        assert ck.saves >= 1
        assert m.checkpoint_syncs > 0
        assert transfer_guarded.transfers == \
            m.host_syncs + m.finalize_syncs + m.checkpoint_syncs
        assert all(r for r in transfer_guarded.reasons())

    def test_pipeline_measured_equals_counted(self):
        from repro.core import ClusterQuotientEstimator, open_session

        with open_session(_graph(), tau=12) as sess:
            with guard.measured_transfers() as meter:
                res = sess.estimate(ClusterQuotientEstimator())
            assert meter.transfers == res.pipeline.total_host_syncs

    def test_cascade_measured_equals_counted(self):
        from repro.core import CascadeEstimator, open_session

        with open_session(_graph(), tau=12) as sess:
            with guard.measured_transfers() as meter:
                res = sess.estimate(CascadeEstimator(levels=2, tau_solve=16))
            assert meter.transfers == res.pipeline.total_host_syncs

    def test_dynamic_update_measured_equals_counted(self):
        from repro.core import UpdateBatch, open_session

        g = _graph()
        with open_session(g, tau=12) as sess:

            def batch(seed):
                r = np.random.default_rng(seed)
                i = r.integers(0, g.n_edges, 4)
                u = r.integers(0, g.n_nodes, 3).astype(np.int32)
                v = r.integers(0, g.n_nodes, 3).astype(np.int32)
                return UpdateBatch(
                    insert_src=u, insert_dst=v,
                    insert_weight=np.full(3, 5, np.int32),
                    reweight_src=g.src[i], reweight_dst=g.dst[i],
                    reweight_weight=np.full(4, 7, np.int32))

            sess.apply_updates(batch(0))   # initializes the dynamic state
            before = sess.dynamic.metrics.update_syncs
            with guard.measured_transfers() as meter:
                sess.apply_updates(batch(1))
            delta = sess.dynamic.metrics.update_syncs - before
            assert meter.transfers == delta
            assert meter.transfers > 0

    def test_fetch_requires_a_reason(self):
        import jax.numpy as jnp

        with pytest.raises(ValueError):
            guard.fetch(jnp.zeros(3), reason="  ")

    def test_nested_meters_both_count(self):
        import jax.numpy as jnp

        with guard.measured_transfers() as outer:
            with guard.measured_transfers() as inner:
                guard.fetch(jnp.arange(4), reason="test: nested fetch")
            guard.fetch(jnp.arange(2), reason="test: outer-only fetch")
        assert inner.transfers == 1 and outer.transfers == 2
        assert outer.elements == 6
