"""Program-build seconds by jitted function.

JAX reports each step of building a program through `jax.monitoring`:
tracing the function to a jaxpr, lowering the jaxpr to an MLIR module,
and the backend compile (a load from the persistent compile cache
fires it too). `install()` records them on the registry, in seconds,
labelled by the function's name:

    jit.trace_s{fun=f}    /jax/core/compile/jaxpr_trace_duration
    jit.lower_s{fun=f}    /jax/core/compile/jaxpr_to_mlir_module_duration
    jit.compile_s{fun=f}  /jax/core/compile/backend_compile_duration

A count rising under ``jit.compile_s{fun=f}`` while serving says which
step recompiled. Tracing one function traces the jitted functions it
calls, and eager work while tracing builds programs of its own; each
event therefore records its own seconds less those of the build events
nested in it on the same thread (JAX records an event's start as a
scalar on entry, which opens the nesting), so the sums of all three
histograms add up to the thread-seconds spent building programs.
"""
from __future__ import annotations

import threading

from . import metrics

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "jit.compile_s",
}

_TLS = threading.local()
_LOCK = threading.Lock()
_INSTALLED = False


def fun_label(fun_name: str) -> str:
    """One label per function across the three events: tracing names
    it ``f``, lowering and compiling ``jit(f)``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _open_events() -> list:
    st = getattr(_TLS, "open", None)
    if st is None:
        st = _TLS.open = []
    return st


def _on_start(event: str, _value, **_kw) -> None:
    if event in EVENTS:
        _open_events().append([event, 0.0])   # [event, nested seconds]


def _on_duration(event: str, secs: float, fun_name: str = "?", **_kw) -> None:
    name = EVENTS.get(event)
    if name is None:
        return
    st = _open_events()
    nested = st.pop()[1] if st and st[-1][0] == event else 0.0
    if st:
        st[-1][1] += secs
    h = metrics.REGISTRY.histogram(name, unit="s", fun=fun_label(str(fun_name)))
    h.observe(max(secs - nested, 0.0))


def install() -> None:
    """Register the listeners, once per process."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        import jax

        jax.monitoring.register_scalar_listener(_on_start)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _INSTALLED = True


__all__ = ["EVENTS", "fun_label", "install"]
