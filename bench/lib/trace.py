"""From a profiler trace to device busy time, kernel time per kind and
the breakdown.

Two steps, kept apart so the second can be checked on a recorded trace:

1. :func:`read_xplane` turns the ``.xplane.pb`` the JAX profiler writes
   into plain events: per device, the operations of its ``XLA Ops``
   line; for the host, every event of every thread.
2. :func:`summarize` reduces those events: busy time is the union of a
   device's operation intervals, averaged over the devices the cell
   uses; a kernel kind's time is the summed duration of the operations
   whose name (or string metadata) matches one of the kind's regular
   expressions in ``bench/kernels.json``; idle gaps are the holes
   between busy intervals, each labelled by the host event that covers
   most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    #: device index -> operations (name, start_ns, dur_ns); the name
    #: carries the op's string metadata after a tab
    devices: Dict[int, List[Event]]
    host: List[Event]

    def to_json(self) -> dict:
        return {"devices": {str(k): v for k, v in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({int(k): [tuple(e) for e in v]
                    for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]])


_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_OPS_LINE = "XLA Ops"


def _stat_text(event) -> str:
    parts = []
    try:
        for k, v in event.stats:
            if isinstance(v, str) and v:
                parts.append(f"{k}={v}")
    except (TypeError, ValueError):
        pass
    return " ".join(parts)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def read_xplane(path: str, devices: Sequence[int]) -> Trace:
    """The events of ``devices`` (their ids) and of the host."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dev: Dict[int, List[Event]] = {d: [] for d in devices}
    host: List[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in dev:
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                dev[int(m.group(1))].extend(
                    (e.name + "\t" + _stat_text(e), float(e.start_ns),
                     float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events)
    return Trace(dev, host)


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted [start, end] intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops: Sequence[Event]) -> float:
    return sum(e - s for s, e in union([(o[1], o[1] + o[2]) for o in ops]))


def load_kinds(path: str) -> Dict[str, List["re.Pattern"]]:
    with open(path) as f:
        return {k: [re.compile(p) for p in ps]
                for k, ps in json.load(f)["kinds"].items()}


def kind_of(name: str, kinds: Dict[str, List["re.Pattern"]]) -> Optional[str]:
    for kind, patterns in kinds.items():
        if any(p.search(name) for p in patterns):
            return kind
    return None


def kernel_ns(trace: Trace, kind: str,
              kinds: Dict[str, List["re.Pattern"]]) -> float:
    """Summed device time of ``kind``'s operations over every device."""
    seen: Dict[str, Optional[str]] = {}
    total = 0.0
    for ops in trace.devices.values():
        for name, _, dur in ops:
            if name not in seen:
                seen[name] = kind_of(name, kinds)
            if seen[name] == kind:
                total += dur
    return total


def _short(name: str) -> str:
    """The head of an operation's HLO text: its name, shapes, opcode."""
    return name.split("\t", 1)[0][:160]


def idle_gaps(trace: Trace, top: int = 10) -> List[List]:
    """The ``top`` longest gaps between busy intervals on the first
    device, each as [label, seconds]: the host event that overlaps most
    of the gap (``"no host event"`` where none does)."""
    if not trace.devices:
        return []
    ops = trace.devices[min(trace.devices)]
    busy = union([(o[1], o[1] + o[2]) for o in ops])
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
                   if b[0] > a[1]), reverse=True)[:top]
    names = [h[0] for h in trace.host]
    hs = np.array([h[1] for h in trace.host], np.float64)
    he = hs + np.array([h[2] for h in trace.host], np.float64)
    out = []
    for length, s, e in gaps:
        label = "no host event"
        if len(names):
            ov = np.minimum(e, he) - np.maximum(s, hs)
            if ov.max() > 0:
                # The shortest event over at least half the gap is the
                # most specific (a thread-long wrapper covers every gap);
                # failing one, the event that overlaps it most.
                cand = np.flatnonzero(ov >= 0.5 * length)
                best = (cand[np.argmin((he - hs)[cand])] if len(cand)
                        else int(np.argmax(ov)))
                label = names[best]
        out.append([label, length * 1e-9])
    return out


def top_ops(trace: Trace, top: int = 10) -> List[List]:
    """Device operations by total time, averaged over devices."""
    tot: Dict[str, float] = {}
    for ops in trace.devices.values():
        for o in ops:
            k = _short(o[0])
            tot[k] = tot.get(k, 0.0) + o[2]
    n = max(len(trace.devices), 1)
    return [[k, v * 1e-9 / n] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def summarize(trace: Trace, window_s: float,
              kinds: Dict[str, List["re.Pattern"]]) -> dict:
    n = max(len(trace.devices), 1)
    busy = sum(busy_ns(ops) for ops in trace.devices.values()) / n * 1e-9
    return {
        "busy_s": busy,
        "window_s": window_s,
        "kernel_s": {k: kernel_ns(trace, k, kinds) * 1e-9 for k in kinds},
        "n_devices": n,
        "breakdown": {"device_ops": top_ops(trace),
                      "idle_gaps": idle_gaps(trace)},
    }
