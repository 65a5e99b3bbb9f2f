"""Named regions of host work on the profiler's clock.

:class:`span` is the one instrumentation call site of the engines'
host work.  While a profiler session runs (``jax.profiler.start_trace``)
it opens a ``jax.profiler.TraceAnnotation``, so the region lands on the
same clock as the device's ops, with ``args`` as the event's stats;
without a session it records nothing and costs one check.  With a
:class:`~repro.obs.Telemetry` and a ``phase`` the same region also
feeds ``phase_seconds`` and the :class:`~repro.obs.TraceRecorder`, so
an operator's Chrome export and the profiler trace name one region.

Args reach the profiler through TraceMe's ``name#key=value,...#``
encoding, which splits on commas: a sequence (of rids) is written as
one space-separated string, a bool as 0 or 1, and ``None`` is left
out.
"""
from __future__ import annotations

import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation


def _arg(v: Any) -> Any:
    if isinstance(v, (list, tuple)):
        return " ".join(str(x) for x in v)
    if isinstance(v, bool):
        return int(v)
    return v


class span:
    """``with span("engine.launch", metrics, phase="fused", rids=...,
    rows=...):`` — a host region named ``name``.

    ``metrics``: ``None`` or a ``Telemetry``; it is fed only when
    ``phase`` names a cost-model phase key (``fused``, ``clip``,
    ``unet_step``, ``vae``), with the region's duration on ``clock``
    (the engine's event clock) under ``engine``, the span's ``rids``
    and its other args.
    """

    __slots__ = ("_ann", "_tele", "_phase", "_engine", "_clock", "_rids",
                 "_args", "_t0")

    def __init__(self, name: str, metrics=None, *, phase: str | None = None,
                 engine: str = "", clock: Callable[[], float] = time.monotonic,
                 rids=(), **args):
        self._ann = None
        if TraceAnnotation.is_enabled():
            if rids:
                args["rids"] = rids
            self._ann = TraceAnnotation(
                name, **{k: _arg(v) for k, v in args.items()
                         if v is not None})
        self._tele = metrics if phase is not None else None
        if self._tele is not None:
            self._phase, self._engine, self._clock = phase, engine, clock
            self._rids = list(rids)
            self._args = {k: v for k, v in args.items() if k != "rids"}

    def __enter__(self) -> "span":
        if self._ann is not None:
            self._ann.__enter__()
        if self._tele is not None:
            self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> None:
        if self._tele is not None:
            self._tele.phase(self._engine, self._phase, self._t0,
                             self._clock(), rids=self._rids,
                             args=self._args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
