"""``chip_smoke.py`` rehearsed on the CPU: the platform gate, and every
phase at a tiny size with the kernels interpreted (the ``relax_impl``
argument of the phase functions is the test-only steer; the script itself
has no such option)."""
import importlib.util
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, SCRIPT], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_phases_rehearse_in_interpret_mode():
    """Also counts the jax arrays that tracing embeds as program constants:
    each is a device->host copy at lowering time, which the transfer guard
    the phases run under refuses on a TPU (on the CPU the guard is inert)."""
    import jax
    from jax._src import array as jax_array
    from jax._src.interpreters import mlir

    embedded = []

    def counting_handler(val, *args, **kwargs):
        embedded.append((val.shape, str(val.dtype)))
        return jax_array._array_mlir_constant_handler(val, *args, **kwargs)

    smoke = _load_smoke()
    jax.clear_caches()
    mlir.register_constant_handler(jax_array.ArrayImpl, counting_handler)
    try:
        a = smoke.phase_a(n=1500, relax_impl="interpret")
        b = smoke.phase_b(n=300, relax_impl="interpret")
    finally:
        mlir.register_constant_handler(
            jax_array.ArrayImpl, jax_array._array_mlir_constant_handler)
    assert a["phi_approx"] >= a["ecc"] > 0
    assert b["lower"] <= b["exact"] <= b["upper"]
    assert embedded == []


def test_chip_smoke_sharded_phase_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        from jax._src import array as jax_array
        from jax._src.interpreters import mlir
        embedded = []
        def counting_handler(val, *args, **kwargs):
            embedded.append(val.shape)
            return jax_array._array_mlir_constant_handler(val, *args,
                                                          **kwargs)
        mlir.register_constant_handler(jax_array.ArrayImpl, counting_handler)
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        out = smoke.phase_sharded(n=1500, relax_impl="interpret")
        print("PHI", out["phi_approx"], "EMBEDDED", len(embedded))
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded: setup_s=" in out.stdout
    assert "plane/edge devices=[4]" in out.stdout
    assert "PHI " in out.stdout
    # no jax array is baked into a program as a constant (see above)
    assert "EMBEDDED 0" in out.stdout
