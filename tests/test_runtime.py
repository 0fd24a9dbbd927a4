"""Runtime substrate: checkpoint roundtrip + elastic restore, compression
telescoping, fault handling, optimizer math, data pipeline determinism."""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save
from repro.config.base import ShapeSpec, TrainConfig, TransformerConfig
from repro.data.pipeline import DataCursor, LMTokenPipeline
from repro.optim import adamw
from repro.runtime.compression import (
    dequantize_int8,
    ef_compress_grads,
    init_residual,
    quantize_int8,
)
from repro.runtime.fault import PreemptionGuard, StragglerMonitor, retriable


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree():
    k = jax.random.PRNGKey(0)
    return {
        "a": jax.random.normal(k, (8, 16)),
        "nested": {"b": jnp.arange(10, dtype=jnp.int32),
                   "c": [jnp.ones(3), jnp.zeros((2, 2))]},
    }


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 7, t, extra={"cursor": {"step": 7, "shard": 1}})
    assert latest_step(str(tmp_path)) == 7
    restored, extra = restore(str(tmp_path), t)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                            np.asarray(b)),
                 t, restored)
    assert extra["cursor"]["step"] == 7


def test_checkpoint_gc_and_latest(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        save(str(tmp_path), s, t, keep=2)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2
    assert latest_step(str(tmp_path)) == 5


def test_checkpoint_atomicity_partial_write(tmp_path):
    """A leftover tmp dir (simulated crash) must not shadow the last good
    checkpoint."""
    t = _tree()
    save(str(tmp_path), 3, t)
    os.makedirs(tmp_path / "tmp.9.999", exist_ok=True)  # dead partial write
    with open(tmp_path / "tmp.9.999" / "garbage.npy", "w") as f:
        f.write("not a checkpoint")
    assert latest_step(str(tmp_path)) == 3
    restored, _ = restore(str(tmp_path), t)
    assert restored is not None


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore with an explicit sharding tree (single-device here; the same
    API re-shards onto any mesh — the dry-run meshes use it)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = _tree()
    save(str(tmp_path), 1, t)
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    restored, _ = restore(str(tmp_path), t, shardings=sh)
    assert restored["a"].sharding == NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_int8_quantization_bounds():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal(1000).astype(np.float32)) * 3
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x).max()
    assert float(err) <= float(s) * 0.5 + 1e-6


def test_error_feedback_telescopes():
    """Sum of EF-compressed grads ~ sum of raw grads: the residual telescopes
    so the cumulative quantization error stays bounded (EF-SGD invariant)."""
    r = np.random.default_rng(1)
    grads = [{"w": jnp.asarray(r.standard_normal(256).astype(np.float32))}
             for _ in range(30)]
    resid = init_residual(grads[0])
    sent_total = jnp.zeros(256)
    raw_total = jnp.zeros(256)
    for g in grads:
        q, s, resid = ef_compress_grads(g, resid)
        sent_total = sent_total + dequantize_int8(q["w"], s["w"])
        raw_total = raw_total + g["w"]
    # cumulative error = final residual, NOT 30x the per-step error
    np.testing.assert_allclose(np.asarray(sent_total + resid["w"]),
                               np.asarray(raw_total), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(sent_total - raw_total).max()) < 0.1


# ---------------------------------------------------------------------------
# fault
# ---------------------------------------------------------------------------

def test_preemption_guard_catches_sigterm():
    with PreemptionGuard() as g:
        assert not g.should_stop
        os.kill(os.getpid(), signal.SIGTERM)
        assert g.should_stop
        assert g.received == signal.SIGTERM


def test_preemption_guard_restores_handlers_on_exit():
    """The guard must put back whatever handlers were installed before it
    — nesting a guard inside launcher-installed handlers (or pytest's)
    must not leak its own handler past the with-block."""
    seen = []
    prev_term = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        with PreemptionGuard() as g:
            assert signal.getsignal(signal.SIGTERM) == g._handler
        assert signal.getsignal(signal.SIGTERM) is not g._handler
        os.kill(os.getpid(), signal.SIGTERM)
        assert seen == [signal.SIGTERM]   # the outer handler is back
        assert not g.should_stop          # the exited guard saw nothing
    finally:
        signal.signal(signal.SIGTERM, prev_term)


def test_preemption_guard_is_not_retriable():
    """Preempted must escape retriable() (the wrapper retries
    RuntimeError): a preemption is a clean exit, never an in-place retry."""
    from repro.runtime.fault import Preempted

    calls = {"n": 0}

    def preempts():
        calls["n"] += 1
        raise Preempted(3, "/tmp/ckpt/step_3")

    with pytest.raises(Preempted) as e:
        retriable(preempts, base_delay=0.001)()
    assert calls["n"] == 1          # no retry
    assert e.value.stage == 3
    assert not isinstance(e.value, RuntimeError)


def test_retriable_retries_then_succeeds():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retriable(flaky, base_delay=0.001)() == "ok"
    assert calls["n"] == 3


def test_retriable_exhausts_with_deterministic_backoff(monkeypatch):
    """Retry count and the doubling backoff schedule are exact: the real
    ``time.sleep`` is patched out, so the test asserts the SCHEDULE
    (0.1, 0.2, 0.4, ...) rather than measuring wall-clock."""
    slept = []
    # det: test patches time.sleep to record the backoff schedule, no real waiting
    monkeypatch.setattr("repro.runtime.fault.time.sleep", slept.append)
    calls = {"n": 0}

    def always_fails():
        calls["n"] += 1
        raise OSError(f"boom {calls['n']}")

    with pytest.raises(OSError, match="boom 4"):
        retriable(always_fails, retries=3, base_delay=0.1)()
    assert calls["n"] == 4                      # 1 try + 3 retries
    assert slept == [0.1, 0.2, 0.4]             # deterministic doubling


def test_straggler_monitor_flags_outliers():
    m = StragglerMonitor(threshold=2.0)
    for i in range(8):
        m.record(i, 0.1)
    assert m.record(8, 0.5)          # 5x EWMA -> straggler
    assert 8 in m.flagged
    assert not m.record(9, 0.11)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    tc = TrainConfig(lr=0.1, warmup=1, weight_decay=0.0, clip_norm=100.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw.init_state(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw.apply_updates(params, opt, g, tc)
    assert float(loss(params)) < 1e-3


def test_adamw_clipping():
    tc = TrainConfig(lr=1e-3, warmup=1, clip_norm=1.0)
    params = {"w": jnp.zeros(4)}
    opt = adamw.init_state(params)
    g = {"w": jnp.full(4, 100.0)}
    _, _, stats = adamw.apply_updates(params, opt, g, tc)
    assert float(stats["grad_norm"]) == pytest.approx(200.0)


def test_zero1_specs_divisible_only():
    from jax.sharding import PartitionSpec as P
    specs = {"a": P(None, "model"), "b": P()}
    shapes = {"a": jax.ShapeDtypeStruct((42, 64), jnp.float32),
              "b": jax.ShapeDtypeStruct((32,), jnp.float32)}
    out = adamw.zero1_state_specs(specs, shapes, axis_size=16)
    assert out["a"] == P(None, "model")      # 42 not divisible -> unchanged
    assert out["b"] == P("data")             # 32 divisible -> sharded


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_replay():
    cfg = TransformerConfig(vocab_size=128)
    shape = ShapeSpec(name="t", kind="train", seq_len=16, global_batch=4)
    p = LMTokenPipeline(cfg, shape, seed=3)
    c = DataCursor(step=5, shard=2)
    b1, b2 = p.batch(c), p.batch(c)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    c2 = DataCursor(step=6, shard=2)
    assert not np.array_equal(p.batch(c2)["tokens"], b1["tokens"])


def test_pipeline_shards_differ():
    cfg = TransformerConfig(vocab_size=128)
    shape = ShapeSpec(name="t", kind="train", seq_len=16, global_batch=4)
    p = LMTokenPipeline(cfg, shape, seed=3)
    a = p.batch(DataCursor(step=0, shard=0))
    b = p.batch(DataCursor(step=0, shard=1))
    assert not np.array_equal(a["tokens"], b["tokens"])


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(env_dir, monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the cache
    goes to the fixed ``<checkout>/.jax_cache``."""
    from repro.common import checkout_root, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(checkout_root(), ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (
            before if env_dir else want)
        assert os.path.isfile(os.path.join(checkout_root(), "ROADMAP.md"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
