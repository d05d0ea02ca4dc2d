"""Lightweight nested spans with a ring buffer of finished traces.

A span measures one stage of work (``with tracer.span("solve",
route="histogram", bucket=64):``). Spans nest: closing a child attaches
it to its parent, closing a root appends the whole tree — as a plain
dict — to the tracer's ring buffer of the last N traces. Exceptions
propagate (the span records ``status="error"`` and the error repr on
the way out, and the stack unwinds correctly).

Device work is asynchronous under JAX, so a span that only brackets the
``launch`` call would time the dispatch, not the math.
:meth:`Span.fence` calls ``jax.block_until_ready`` on a launch result
and records the span-start -> ready interval as ``device_s`` — the
fenced device time — while returning the value, so the call site stays
one expression: ``outs = sp.fence(prog.launch(*inputs))``.

Every span, enabled or not, is also a ``jax.profiler.TraceAnnotation``
named ``fcm.<name>``, so a profiler trace shows the engine's stages on
the device's clock (about a microsecond a span with no profiler
session active). Only the attrs a span names in ``annotate`` ride on
the annotation; request ids stay in the ring's ``attrs``.

``Tracer(enabled=False)`` keeps timing semantics (spans still measure,
``fence`` still blocks, annotations still reach the profiler) but skips
the ring buffer — what the tracing-overhead benchmark compares against.
Callers feed their own counters from ``Span.wall_s``.
"""
from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager
from typing import Any, Deque, Dict, List, Optional, Sequence

from jax.profiler import TraceAnnotation

from . import metrics as M

__all__ = ["Span", "Tracer"]


class Span:
    """One timed stage. ``wall_s`` is set when the span closes;
    ``device_s`` only when :meth:`fence` ran inside it."""

    __slots__ = ("name", "attrs", "t_start", "wall_s", "device_s",
                 "status", "error", "children", "_t0")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.t_start = time.time()
        self._t0 = time.perf_counter()
        self.wall_s: Optional[float] = None
        self.device_s: Optional[float] = None
        self.status = "ok"
        self.error: Optional[str] = None
        self.children: List["Span"] = []

    def fence(self, value):
        """Block until ``value``'s device work is ready; record the
        span-start -> ready interval as this span's device time."""
        import jax
        value = jax.block_until_ready(value)
        self.device_s = time.perf_counter() - self._t0
        return value

    def close(self, error: Optional[BaseException] = None) -> None:
        self.wall_s = time.perf_counter() - self._t0
        if error is not None:
            self.status = "error"
            self.error = repr(error)

    def to_dict(self) -> dict:
        d = {"name": self.name, "t_start": self.t_start,
             "wall_s": self.wall_s, "status": self.status}
        if self.attrs:
            d["attrs"] = M.json_safe(self.attrs)
        if self.device_s is not None:
            d["device_s"] = self.device_s
        if self.error is not None:
            d["error"] = self.error
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


class Tracer:
    """Span factory + ring buffer of the last ``max_traces`` root
    traces. The span stack is thread-local; the ring is shared."""

    def __init__(self, max_traces: int = 64, enabled: bool = True):
        self.enabled = enabled
        self._ring: Deque[dict] = collections.deque(maxlen=max_traces)
        self._local = threading.local()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @property
    def current_span(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    @contextmanager
    def span(self, name: str, ring: bool = True,
             annotate: Sequence[str] = (), **attrs):
        """Open a timed span, annotated in the profiler trace as
        ``fcm.<name>`` with the attrs ``annotate`` names. ``ring=False``
        keeps a root span out of the trace ring (per-submit validation
        spans would otherwise drown the flush traces) while still
        timing. Exceptions mark the span ``status="error"`` and
        propagate."""
        sp = Span(name, attrs)
        stack = self._stack()
        stack.append(sp)
        try:
            with TraceAnnotation("fcm." + name,
                                 **{k: attrs[k] for k in annotate}):
                yield sp
        except BaseException as e:
            sp.close(e)
            raise
        finally:
            if sp.wall_s is None:       # non-error exit
                sp.close()
            stack.pop()
            if stack:
                stack[-1].children.append(sp)
            elif ring and self.enabled:
                self._ring.append(sp.to_dict())

    def traces(self) -> List[dict]:
        """The finished root traces, oldest first (plain dicts)."""
        return list(self._ring)

    def clear(self) -> None:
        self._ring.clear()
