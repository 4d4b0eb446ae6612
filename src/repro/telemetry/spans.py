"""Request-scoped spans on the profiler's clock.

A request scope (``request``) collects the host time of every ``span``
opened inside it into one ``dict[str, float]`` (seconds, summed per span
name), which the request's ``TelemetryRecord.spans`` holds. Each span is
also a ``jax.profiler.TraceAnnotation`` named ``repro.<name>`` and
carrying the request id, so under a running profiler it lands on the host
plane of the same trace as the device's operations, on the same clock.

Spans time the host: a span around an asynchronous dispatch measures the
dispatch, not the device work it queues. Stage spans that must cover the
device work end in a ``block_until_ready`` of their own (``.wait``
children in ``core/pipeline.py``).

With no profiler running, a span costs one ``perf_counter`` pair, one
dict update and an inactive TraceMe; nothing turns it off. With no
request open, a span only annotates.

``install_gc_hook`` adds a ``gc.callbacks`` hook (once per process):
each collection becomes a ``repro.gc`` annotation with its generation,
and its pause is added to the open request's ``gc`` entry.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import gc
import time
from typing import Iterator, Optional

from jax.profiler import TraceAnnotation

PREFIX = "repro."


@dataclasses.dataclass
class _Scope:
    rid: Optional[int]
    spans: dict = dataclasses.field(default_factory=dict)


_SCOPE: contextvars.ContextVar[Optional[_Scope]] = contextvars.ContextVar(
    "repro_request_scope", default=None
)


@contextlib.contextmanager
def request(rid: Optional[int] = None) -> Iterator[dict]:
    """Open a request scope for ``rid``; yields the dict its spans fill.
    Scopes nest: the inner one collects until it closes."""
    scope = _Scope(rid)
    token = _SCOPE.set(scope)
    try:
        yield scope.spans
    finally:
        _SCOPE.reset(token)


def in_request() -> bool:
    """Whether a request scope is open in this context."""
    return _SCOPE.get() is not None


def recorded() -> dict:
    """The open request's span dict (a new empty one when none is open):
    a record built inside the scope holds the very dict later spans fill."""
    scope = _SCOPE.get()
    return {} if scope is None else scope.spans


def _add(spans: dict, name: str, seconds: float) -> None:
    spans[name] = spans.get(name, 0.0) + seconds


class span:
    """Time ``name`` into the open request and annotate it as
    ``repro.<name>`` (``meta`` rides along as trace metadata).
    ``seconds`` holds the duration once the block has closed."""

    __slots__ = ("name", "meta", "seconds", "_ann", "_spans", "_t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta
        self.seconds = 0.0

    def __enter__(self) -> "span":
        scope = _SCOPE.get()
        self._spans = None if scope is None else scope.spans
        if scope is not None and scope.rid is not None:
            self.meta["request_id"] = scope.rid
        self._ann = TraceAnnotation(PREFIX + self.name, **self.meta)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._spans is not None:
            _add(self._spans, self.name, self.seconds)


class _GcHook:
    """``gc.callbacks`` entry: one ``repro.gc`` annotation per collection,
    its pause added to the open request's ``gc`` entry. A collection runs
    to its end in the thread that started it, so start and stop pair up."""

    def __init__(self):
        self._open: Optional[tuple[TraceAnnotation, float]] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = TraceAnnotation(PREFIX + "gc", generation=info["generation"])
            ann.__enter__()
            self._open = (ann, time.perf_counter())
        elif self._open is not None:
            ann, t0 = self._open
            self._open = None
            seconds = time.perf_counter() - t0
            ann.__exit__(None, None, None)
            scope = _SCOPE.get()
            if scope is not None:
                _add(scope.spans, "gc", seconds)


_GC_HOOK = _GcHook()


def install_gc_hook() -> None:
    """Add the collector hook to ``gc.callbacks`` unless it is there."""
    if _GC_HOOK not in gc.callbacks:
        gc.callbacks.append(_GC_HOOK)
