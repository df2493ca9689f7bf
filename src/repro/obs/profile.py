"""Names on the profiler's clock: device scopes, host spans, compile marks.

Where :mod:`repro.obs.spans` records the PS simulator's *virtual* time,
this module puts names into ``jax.profiler``'s own trace, whose host and
device planes share one wall clock:

* :func:`stage` — a ``jax.named_scope`` for one stage of the epoch
  (:data:`~repro.obs.names.DEVICE_SCOPES`). It only changes the
  ``op_name`` metadata of the instructions traced inside it, so it costs
  nothing at run time; a trace reduction maps each device instruction to
  its stage through the compiled program's HLO text.
* :func:`host_span` — a ``jax.profiler.TraceAnnotation`` around host work
  of the PS runtime (:data:`~repro.obs.names.HOST_SPANS`). With no
  profiler active it costs about a microsecond; it consumes no rng,
  schedules nothing and reads no state, so a run with spans is bitwise
  the run without.
* :func:`install_compile_marker` — a ``jax.monitoring`` listener that
  emits a zero-length ``asybadmm.compile`` host span (with ``fun_name``
  and ``seconds``) each time a backend compile (or persistent-cache load)
  ends, so a compile inside a traced window shows on the trace, named.

    with stage("asybadmm.gather"):
        z_tilde = gather_delayed(z_hist, delays)
    with host_span("ps.sync.loss", round=t, worker=i):
        value = float(loss)
"""
from __future__ import annotations

import jax

from .names import DEVICE_SCOPES, HOST_SPANS, validate_kind

COMPILE = "asybadmm.compile"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_installed = False


def stage(name: str):
    """``jax.named_scope(name)`` for a registered epoch stage."""
    return jax.named_scope(validate_kind(name, DEVICE_SCOPES,
                                         "device scope"))


def host_span(name: str, **meta):
    """``jax.profiler.TraceAnnotation(name, **meta)`` for a registered
    host span."""
    return jax.profiler.TraceAnnotation(
        validate_kind(name, HOST_SPANS, "host span"), **meta)


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT:
        with jax.profiler.TraceAnnotation(
                COMPILE, fun_name=str(kwargs.get("fun_name", "")),
                seconds=seconds):
            pass


def install_compile_marker() -> None:
    """Register the compile listener once per process (idempotent)."""
    global _installed
    if not _installed:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
