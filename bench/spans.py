"""The program's spans on the profiler's clock, with no program change.

``ProfiledTracer`` is a ``repro.runtime.telemetry.Tracer`` that also opens a
``jax.profiler.TraceAnnotation`` for each span it records, so that a
profiler trace holds the host's spans beside the device's operations. With
no profiler running an annotation costs next to nothing.
"""
from __future__ import annotations


def profiled_tracer():
    import jax
    from repro.runtime import telemetry

    class _Annotated:
        __slots__ = ("_span", "_note")

        def __init__(self, span, name):
            self._span = span
            self._note = jax.profiler.TraceAnnotation(name)

        def __enter__(self):
            self._note.__enter__()
            return self._span.__enter__()

        def __exit__(self, *exc):
            try:
                return self._span.__exit__(*exc)
            finally:
                self._note.__exit__(*exc)

    class ProfiledTracer(telemetry.Tracer):
        def span(self, name, **attrs):
            return _Annotated(super().span(name, **attrs), name)

    return ProfiledTracer()
