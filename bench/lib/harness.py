"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

The system under test is ``FCMServeEngine``, driven through
``submit_async`` exactly as a client would. From the program the run
takes only the engine, its counters and the names of its kernels;
everything it measures with (traffic, reference, costs, peaks, trace
reduction) lives in ``bench/``.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import compare, costs, traffic as T
from . import trace as TR
from .spec import Cell, reader

SAMPLE = 256                  # requests compared with the reference
RESOLVE_GRACE_S = 60.0        # wait past the window's close for answers


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def semantics(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The FCM / FCM_S parameters the configuration states."""
    return dict(cfg["fcm"])


def build_engine(cfg: Dict[str, Any], devices):
    import jax
    from repro.core.fcm import FCMConfig
    from repro.core.spatial import SpatialFCMConfig
    from repro.serving.fcm_engine import FCMServeEngine

    s = semantics(cfg)
    base = dict(n_clusters=s["n_clusters"], m=s["m"], eps=s["eps"],
                max_iters=s["max_iters"])
    spatial = SpatialFCMConfig(**base, alpha=s["alpha"],
                               neighbors=s["neighbors"] or 4)
    mesh = None
    if cfg.get("mesh_data"):
        n = int(cfg["mesh_data"])
        mesh = jax.make_mesh((n,), ("data",), devices=devices[:n])
    return FCMServeEngine(FCMConfig(**base),
                          batch_sizes=tuple(cfg["batch_sizes"]),
                          spatial_cfg=spatial,
                          cache_size=int(cfg["cache_size"]),
                          tracing=False,
                          max_wait_ms=float(cfg["max_wait_ms"]),
                          mesh=mesh)


def warm(eng, route: str, pool: T.Pool) -> None:
    """Compile (or load from the cache) every bucket of the cell's route
    at the cell's shape, twice, then once through the async front door
    so the flusher thread is running."""
    k = 0
    for _ in range(2):
        for b in eng.batch_sizes:
            for _ in range(b):
                eng.submit(pool.slice(k // pool.n_slices, k), method=route)
                k += 1
            eng.flush()
    futs = [eng.submit_async(pool.slice(0, i), method=route)
            for i in range(eng.batch_sizes[-1])]
    for f in futs:
        f.result(timeout=600)


_COUNTERS = {"images": ("route.images", {}), "padded": ("route.padded", {}),
             "batches": ("route.batches", {}),
             "solve_s": ("route.stage_seconds", {"stage": "solve"}),
             "materialize_s": ("route.stage_seconds",
                               {"stage": "materialize"})}


def read_counters(eng, route: str) -> Dict[str, float]:
    out = {k: float(eng.metrics.counter(name, route=route, **lab).value)
           for k, (name, lab) in _COUNTERS.items()}
    h = eng.metrics.peek("route.lane_iters", route=route)
    out["lane_iters_sum"] = float(h.total) if h else 0.0
    out["lane_iters_count"] = float(h.count) if h else 0.0
    return out


class CompileCounter:
    """Counts JAX compiles and traces (a new program) while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[-1]] += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What the window produced."""
    latency_s: np.ndarray         # per request due in the window
    ok: np.ndarray                # resolved with a result
    iters: List[int]              # every request served
    submit_s: List[float]         # host time inside submit_async
    late_s: List[float]           # generator lateness against the schedule
    completed_in_window: int
    kept: List[tuple]             # (study, slice, centers, labels, iters)


def _wait(fut, timeout: float) -> bool:
    """True once ``fut`` resolved (with a result or an error)."""
    try:
        fut.result(max(timeout, 0.0))
    except TimeoutError:
        return False
    except Exception:       # noqa: BLE001 -- resolved with an error
        pass
    return True


def _record(fut, result_sink, keep):
    """(resolve time, ok, iters) of a resolved future; copies the answer
    into ``result_sink`` when ``keep`` is a (study, slice) pair."""
    err = fut.exception()
    if err is not None:
        return fut.resolve_t, False, None
    r = fut.result(0)
    if keep is not None:
        result_sink.append((keep[0], keep[1], np.array(r.centers, np.float32),
                            np.array(r.labels), int(r.n_iters)))
    return fut.resolve_t, True, int(r.n_iters)


def drive_open(eng, route, pool, sched: T.OpenSchedule, seconds: float,
               seed: int) -> tuple:
    """Submit on the schedule from the caller's thread; returns (window
    start, Served). Latency runs from each request's due time."""
    n = len(sched.due_s)
    rng = np.random.default_rng([seed, 5])
    sampled = set(rng.choice(n, size=min(SAMPLE, n), replace=False).tolist())
    lat = np.full(n, np.inf)
    ok = np.zeros(n, bool)
    iters: List[int] = []
    submit_s = [0.0] * n
    late_s = [0.0] * n
    kept: List[tuple] = []
    pending: "collections.deque" = collections.deque()
    t0 = time.perf_counter() + 0.01
    due = t0 + sched.due_s

    def reap(j, fut):
        rt, good, it = _record(
            fut, kept, (int(sched.study[j]), int(sched.slice[j]))
            if j in sampled else None)
        lat[j] = rt - due[j] if good else np.inf
        ok[j] = good
        if it is not None:
            iters.append(it)

    for i in range(n):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        img = pool.slice(int(sched.study[i]), int(sched.slice[i]))
        t = time.perf_counter()
        fut = eng.submit_async(img, method=route)
        submit_s[i] = time.perf_counter() - t
        late_s[i] = t - due[i]
        pending.append((i, fut))
        while pending and pending[0][1].done():
            reap(*pending.popleft())
    close = t0 + seconds
    give_up = max(close, time.perf_counter()) + RESOLVE_GRACE_S
    for j, fut in pending:
        left = give_up - time.perf_counter()
        if _wait(fut, left):
            reap(j, fut)
    # A request that failed or never answered counts as answered when
    # the run gave up on it: past every tail.
    lat = np.where(np.isfinite(lat), lat, give_up - due)
    done = [due[j] + lat[j] for j in range(n) if ok[j]]
    in_window = sum(1 for t in done if t <= close)
    return t0, Served(lat, ok, iters, submit_s, late_s, in_window, kept)


def drive_closed(eng, route, pool, clients: int, seconds: float,
                 seed: int) -> tuple:
    """``clients`` threads, each submitting a whole study at once and
    waiting for all of it before the next. Latency runs from the study's
    submission; the rate counts answers that came inside the window."""
    order = T.closed_order(pool, clients, seed)
    per_client = max(SAMPLE // clients, 1)
    t0 = time.perf_counter() + 0.05
    close = t0 + seconds
    give_up = close + RESOLVE_GRACE_S
    out = [dict(lat=[], ok=[], done=[], iters=[], submit=[], kept=[])
           for _ in range(clients)]

    def client(c: int) -> None:
        o = out[c]
        rng = np.random.default_rng([seed, 6, c])
        seen = 0
        time.sleep(max(t0 - time.perf_counter(), 0.0))
        j = 0
        while time.perf_counter() < close:
            study = int(order[c][j % len(order[c])])
            j += 1
            ts = time.perf_counter()
            futs = []
            for k in range(pool.n_slices):
                t = time.perf_counter()
                futs.append(eng.submit_async(pool.slice(study, k),
                                             method=route))
                o["submit"].append(time.perf_counter() - t)
            for k, fut in enumerate(futs):
                if not _wait(fut, give_up - time.perf_counter()):
                    o["lat"].append(give_up - ts)
                    o["ok"].append(False)
                    continue
                # Reservoir sample of this client's answers.
                slot = None
                if len(o["kept"]) < per_client:
                    slot = len(o["kept"])
                else:
                    r = int(rng.integers(0, seen + 1))
                    slot = r if r < per_client else None
                seen += 1
                sink: List[tuple] = []
                rt, good, it = _record(fut, sink,
                                       (study, k) if slot is not None
                                       else None)
                if sink:
                    if slot < len(o["kept"]):
                        o["kept"][slot] = sink[0]
                    else:
                        o["kept"].append(sink[0])
                o["lat"].append(rt - ts if good else give_up - ts)
                o["ok"].append(good)
                if good:
                    o["done"].append(rt)
                    o["iters"].append(it)

    threads = [threading.Thread(target=client, args=(c,), name=f"client{c}")
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    cat = lambda key: [x for o in out for x in o[key]]  # noqa: E731
    done = cat("done")
    return t0, Served(np.asarray(cat("lat")), np.asarray(cat("ok"), bool),
                      cat("iters"), cat("submit"), [],
                      sum(1 for t in done if t0 <= t <= close),
                      cat("kept"))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Options:
    seed: int
    seconds: float
    trace: bool
    t_process: float                  # perf_counter at process start
    peak: Optional[Dict[str, Any]] = None
    #: test-only knobs: smaller slices, a lower rate, a planted fault
    height: Optional[int] = None
    width: Optional[int] = None
    rate_scale: float = 1.0
    fault: Optional[Callable] = None
    control: bool = False             # the reference in the program's place
    trace_dir: Optional[str] = None   # keep the profiler trace here (tools)


def stalls(due_s: np.ndarray, late_s: List[float], over: float = 0.02,
           top: int = 8) -> List[List[float]]:
    """Episodes in which the generator ran ``over`` seconds late or more:
    [offset of the first late request's due time, the most it ran late],
    the ``top`` worst in time order."""
    late = np.asarray(late_s)
    eps: List[List[float]] = []
    prev = -2
    for i in np.flatnonzero(late >= over):
        if i == prev + 1:
            eps[-1][1] = max(eps[-1][1], float(late[i]))
        else:
            eps.append([round(float(due_s[i]), 3), float(late[i])])
        prev = i
    worst = sorted(eps, key=lambda e: -e[1])[:top]
    return [[o, round(x, 4)] for o, x in sorted(worst)]


def percentile(xs: np.ndarray, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run_cell(cell: Cell, opt: Options, devices) -> Dict[str, Any]:
    import jax

    cfg, mix, route = cell.config, cell.traffic, cell.config["route"]
    marks = [("start", time.perf_counter())]
    pool = T.make_pool(cfg, mix, opt.seed, opt.height, opt.width)
    marks.append(("traffic", time.perf_counter()))
    eng = build_engine(cfg, devices)
    if opt.fault is not None:
        opt.fault(eng)
    marks.append(("engine", time.perf_counter()))
    warm(eng, route, pool)
    marks.append(("warm-up", time.perf_counter()))
    # What set-up made (JAX, the programs, the traffic) stays alive for
    # the whole run: freeze it out of the collector, as a server would
    # after its warm-up, so the window's collections scan only what the
    # window makes.
    gc.collect()
    gc.freeze()
    compiles = CompileCounter()

    if mix["loop"] == "open":
        rate = float(cell.params["rate_per_s"]) * opt.rate_scale
        sched = T.open_schedule(rate, opt.seconds, pool, opt.seed)
    before = read_counters(eng, route)
    trace_dir = opt.trace_dir or (tempfile.mkdtemp(prefix="bench-trace-")
                                  if opt.trace else None)
    if opt.trace:
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=po)
    t_trace = time.perf_counter()
    compiles.armed = True
    if mix["loop"] == "open":
        t0, served = drive_open(eng, route, pool, sched, opt.seconds,
                                opt.seed)
    else:
        t0, served = drive_closed(eng, route, pool, int(mix["clients"]),
                                  opt.seconds, opt.seed)
    setup_s = t0 - opt.t_process
    compiles.armed = False
    compiles.close()
    window_s = time.perf_counter() - t_trace
    if opt.trace:
        jax.profiler.stop_trace()
    after = read_counters(eng, route)
    used = devices[:cell.chips]
    mem = [((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
           for d in used]
    st = eng.stats()
    eng.shutdown()
    del eng

    attempted = int(len(served.ok))
    failed = int(attempted - served.ok.sum())
    log(f"route_impls {st['route_impls']}")
    log(f"fault_tolerance {st['fault_tolerance']}")
    log(f"samples: {attempted} requests due in the window, {failed} failed, "
        f"{served.completed_in_window} completed inside it, "
        f"{len(served.kept)} kept for the comparison")
    marks.append(("to the window", t0))
    log("set-up: process start to run " + f"{marks[0][1] - opt.t_process:.3f} s"
        + "".join(f", {name} {b - a:.3f} s"
                  for (_, a), (name, b) in zip(marks, marks[1:])))
    if served.late_s:
        late = served.late_s
        log(f"generator lateness: median {statistics.median(late):.6f} s, "
            f"max {max(late):.6f} s")
        log("generator stalls over 20 ms (window offset s, lateness s): "
            f"{stalls(sched.due_s, late)}")
    log(f"compiles inside the window: {dict(compiles.counts)}")

    checks = compare.check(cell, pool, served.kept, failed,
                           control=opt.control)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    lat_ms = served.latency_s * 1e3
    values = {"p95_ms": percentile(lat_ms, 95),
              "p50_ms": percentile(lat_ms, 50),
              "requests_per_s": served.completed_in_window / opt.seconds,
              "setup_s": setup_s}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": attempted, "failed": failed}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(max(mem))}
    if opt.trace:
        trace = TR.read_xplane(TR.find_xplane(trace_dir),
                               [d.id for d in used])
        if not opt.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        summary = TR.summarize(trace, window_s, TR.load_kinds(
            os.path.join(cell.bench_dir, "kernels.json")))
        ctx = Context(cell, before, after, served, summary, opt.peak)
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"], cell.bench_dir)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=window_s)
        result.update(metrics=metrics, device=device,
                      breakdown=summary["breakdown"])
    else:
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                            "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=device)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: Cell
    before: Dict[str, float]          # engine counters at the window's start
    after: Dict[str, float]           # ... once every answer came
    served: Served
    trace: Dict[str, Any]             # trace.summarize of the traced span
    peak: Optional[Dict[str, Any]]

    def delta(self, key: str) -> float:
        return self.after[key] - self.before[key]

    @property
    def shape(self):
        return self.cell.config["height"], self.cell.config["width"]

    def kernel_seconds(self, kind: str) -> float:
        return self.trace["kernel_s"].get(kind, 0.0)

    def roofline_share(self, kind: str) -> Optional[float]:
        """Percent of the least time the cell's work of ``kind`` needs on
        one chip, over the device time its kernels took on all chips;
        None where no kernel of that kind ran."""
        k_s = self.kernel_seconds(kind)
        if k_s <= 0:
            return None
        h, w = self.shape
        sem = semantics(self.cell.config)
        flops, bytes_ = costs.kernel_work(
            kind, iters=self.served.iters, c=sem["n_clusters"],
            pixels=h * w, rows=int(self.cell.config["n_bins"]),
            neighbors=sem["neighbors"])
        t, bound = costs.roofline_seconds(flops, bytes_, self.peak)
        log(f"roofline {kind}: {flops:.6g} FLOP, {bytes_:.6g} bytes, "
            f"least {t:.6g} s ({bound}-bound), kernels {k_s:.6g} s")
        return 100.0 * t / k_s
