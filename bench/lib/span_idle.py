"""Device idle time split by the engine span open over it.

The engine's spans reach a profiler trace as host events named
``fcm.<span>`` (``repro.obs.Tracer``): ``fcm.flush`` > ``fcm.bucket`` >
``fcm.gather``, ``fcm.launch`` > ``fcm.h2d``, ``fcm.scatter`` >
``fcm.d2h``. :func:`idle_by_span` gives each piece of device idle time
to the innermost engine span open over it inside an ``fcm.flush``, and
idle with no flush open to ``OUTSIDE_FLUSH``. ``fcm.ingest`` runs on
client threads beside the flusher's spans, not inside them, so it
labels nothing.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from .trace import Trace, union

ENGINE_PREFIX = "fcm."
FLUSH_SPAN = "fcm.flush"
CLIENT_SPANS = ("fcm.ingest",)
OUTSIDE_FLUSH = "outside flush"


def span_segments(trace: Trace) -> List[Tuple[float, float, str]]:
    """The host clock cut at every engine span's edges, each piece
    labelled by the innermost engine span open over it (the latest
    started) where an ``fcm.flush`` is open, ``OUTSIDE_FLUSH`` where
    none is."""
    spans = [(s, s + d, n) for n, s, d in trace.host
             if n.startswith(ENGINE_PREFIX) and n not in CLIENT_SPANS
             and d > 0]
    # At one instant, spans close before others open.
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(spans)])
    open_: Dict[int, Tuple[float, float, str]] = {}
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, opening, i in edges:
        if prev is not None and t > prev and open_:
            label = OUTSIDE_FLUSH
            if any(sp[2] == FLUSH_SPAN for sp in open_.values()):
                label = max(open_.values(), key=lambda sp: (sp[0], -sp[1]))[2]
            out.append((prev, t, label))
        prev = t
        if opening:
            open_[i] = spans[i]
        else:
            open_.pop(i, None)
    return out


def idle_by_span(trace: Trace) -> Dict[str, float]:
    """Device idle seconds, averaged over the devices, by the innermost
    engine span open inside an ``fcm.flush``; idle while no flush is
    open goes under ``OUTSIDE_FLUSH``. Idle is the holes between busy
    intervals over the traced span (the first to the last event of any
    plane). Empty where the trace holds no ``fcm.flush`` (a program
    without engine spans) or no device."""
    if not trace.devices or not any(n == FLUSH_SPAN for n, _, _ in trace.host):
        return {}
    segs = span_segments(trace)
    everything = [e for ops in trace.devices.values() for e in ops] \
        + list(trace.host)
    t0 = min(e[1] for e in everything)
    t1 = max(e[1] + e[2] for e in everything)
    out: Dict[str, float] = {OUTSIDE_FLUSH: 0.0}
    for ops in trace.devices.values():
        idle, at = [], t0
        for s, e in union([(o[1], o[1] + o[2]) for o in ops]):
            if s > at:
                idle.append((at, s))
            at = max(at, e)
        if at < t1:
            idle.append((at, t1))
        labelled = 0.0
        j = 0
        for s, e in idle:
            while j < len(segs) and segs[j][1] <= s:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < e:
                ov = min(e, segs[k][1]) - max(s, segs[k][0])
                if ov > 0:
                    out[segs[k][2]] = out.get(segs[k][2], 0.0) + ov
                    labelled += ov
                k += 1
        out[OUTSIDE_FLUSH] += sum(e - s for s, e in idle) - labelled
    n = len(trace.devices)
    return {k: v / n * 1e-9 for k, v in out.items()}
