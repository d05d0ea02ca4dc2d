"""Observability layer: histogram quantiles vs numpy percentiles, span
nesting/exception safety, JSON-safety, registry reset semantics, and
solver convergence telemetry (incl. per-lane parity on ragged
batches)."""
import json
import math

import numpy as np
import pytest

from repro import obs
from repro.core import fcm as F
from repro.core import solver as SV
from repro.data import phantom


# ---------------------------------------------------------------------------
# Histogram quantiles
# ---------------------------------------------------------------------------

def test_latency_quantiles_match_numpy_within_bucket_width():
    """Fixed log buckets (10^(1/8) steps): the interpolated quantile
    must land within one bucket ratio of the exact numpy percentile."""
    rng = np.random.default_rng(0)
    samples = np.exp(rng.normal(math.log(5e-3), 1.0, size=5000))
    h = obs.Histogram(obs.LATENCY_EDGES)
    for v in samples:
        h.record(v)
    ratio = 10.0 ** (1.0 / 8.0)
    for q in (0.50, 0.90, 0.99):
        exact = float(np.percentile(samples, 100 * q))
        got = h.quantile(q)
        assert exact / ratio <= got <= exact * ratio, (q, got, exact)


def test_iter_quantiles_exact_to_one_iteration():
    """Unit-spaced edges through 64: quantiles good to +-1 iter."""
    rng = np.random.default_rng(1)
    samples = rng.integers(1, 60, size=2000)
    h = obs.Histogram(obs.ITER_EDGES)
    for v in samples:
        h.record(int(v))
    for q in (0.50, 0.90, 0.99):
        exact = float(np.percentile(samples, 100 * q))
        assert abs(h.quantile(q) - exact) <= 1.0


def test_histogram_quantiles_clamped_to_observed_range():
    h = obs.Histogram(edges=(1.0, 2.0, 4.0))
    for v in (0.25, 0.25, 8.0):              # under- and overflow buckets
        h.record(v)
    assert h.quantile(0.0) >= 0.25
    assert h.quantile(1.0) <= 8.0
    s = h.snapshot()
    assert s["min"] == 0.25 and s["max"] == 8.0 and s["count"] == 3


def test_overflow_p99_tracks_numpy_not_last_edge():
    """Regression: p99 used to clamp at edges[-1] once samples spilled
    into the overflow bucket (easy with ITER_EDGES when max_iters
    exceeds the unit-spaced range). The overflow bucket's upper bound
    is the tracked vmax, so the interpolated quantile must stay within
    the overflow bucket's width of the exact numpy percentile — far
    beyond the last edge, not pinned to it."""
    last = obs.ITER_EDGES[-1]                # 512
    rng = np.random.default_rng(2)
    samples = rng.integers(last + 100, last + 500, size=4000)
    h = obs.Histogram(obs.ITER_EDGES)
    for v in samples:
        h.record(int(v))
    got, over = h.quantile_info(0.99)
    assert over is True
    assert got > last                        # not clamped at the edge
    exact = float(np.percentile(samples, 99))
    # one-bucket error bound: everything landed in [edges[-1], vmax]
    assert abs(got - exact) <= samples.max() - last
    s = h.snapshot()
    assert s["p99"] == got and s["p99_overflow"] is True


def test_quantiles_inside_edges_are_not_overflow_flagged():
    h = obs.Histogram(obs.ITER_EDGES)
    for v in (3, 5, 7, 9, 520):              # one overflow sample
        h.record(v)
    s = h.snapshot()
    assert s["p50_overflow"] is False
    got, over = h.quantile_info(1.0)         # the max IS the overflow
    assert over is True and 512 < got <= 520


def test_empty_histogram_snapshot_is_none_safe():
    s = obs.Histogram(obs.LATENCY_EDGES).snapshot()
    assert s["count"] == 0
    assert s["mean"] is None and s["p50"] is None and s["p99"] is None
    json.dumps(s)                            # and it serializes


def test_histogram_rejects_bad_edges_and_bad_q():
    with pytest.raises(ValueError):
        obs.Histogram(edges=(1.0, 1.0, 2.0))
    h = obs.Histogram(edges=(1.0, 2.0))
    h.record(1.5)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_exact_mean_and_sum():
    h = obs.Histogram(obs.LATENCY_EDGES)
    for v in (0.001, 0.002, 0.003):
        h.record(v)
    assert h.snapshot()["sum"] == pytest.approx(0.006)
    assert h.snapshot()["mean"] == pytest.approx(0.002)


# ---------------------------------------------------------------------------
# Counters / gauges / registry
# ---------------------------------------------------------------------------

def test_counter_stays_python_int_for_int_feeds():
    c = obs.Counter()
    c.inc()
    c.inc(3)
    assert c.snapshot() == 4 and type(c.snapshot()) is int
    c.inc(0.5)                               # stage seconds -> float
    assert isinstance(c.snapshot(), float)


def test_registry_labels_key_distinct_metrics():
    reg = obs.MetricsRegistry()
    reg.counter("req", route="a").inc()
    reg.counter("req", route="b").inc(2)
    assert reg.counter("req", route="a").value == 1
    assert reg.counter("req", route="b").value == 2
    snap = reg.snapshot()
    assert snap["counters"]["req{route=a}"] == 1
    assert snap["counters"]["req{route=b}"] == 2


def test_registry_type_conflict_raises():
    reg = obs.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_reset_zeroes_in_place_keeping_schema():
    reg = obs.MetricsRegistry()
    c = reg.counter("n")
    g = reg.gauge("g")
    h = reg.histogram("h", edges=obs.ITER_EDGES, kind="flat")
    c.inc(7)
    g.set(3.5)
    h.record(12)
    reg.reset()
    assert c.value == 0 and g.value == 0.0 and h.count == 0
    snap = reg.snapshot()                    # keys survive the reset
    assert set(snap["counters"]) == {"n"}
    assert set(snap["histograms"]) == {"h{kind=flat}"}
    assert snap["histograms"]["h{kind=flat}"]["count"] == 0
    # the reset histogram still records into the same object
    reg.histogram("h", edges=obs.ITER_EDGES, kind="flat").record(3)
    assert h.count == 1


def test_registry_peek_never_creates():
    reg = obs.MetricsRegistry()
    assert reg.peek("nope", route="x") is None
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}
    reg.counter("yes").inc()
    assert reg.peek("yes").value == 1


def test_registry_to_json_round_trips():
    reg = obs.MetricsRegistry()
    reg.histogram("lat").record(0.01)
    reg.gauge("depth").set(2)
    assert json.loads(reg.to_json())["gauges"]["depth"] == 2.0


# ---------------------------------------------------------------------------
# json_safe
# ---------------------------------------------------------------------------

def test_json_safe_coerces_numpy_scalars_and_arrays():
    out = obs.json_safe({"a": np.float32(1.5), "b": np.int64(3),
                         "c": np.arange(3), "d": (1, 2),
                         "e": np.bool_(True)})
    json.dumps(out)
    assert out == {"a": 1.5, "b": 3, "c": [0, 1, 2], "d": [1, 2],
                   "e": True}
    assert type(out["b"]) is int


def test_json_safe_raises_on_unserializable():
    with pytest.raises(TypeError):
        obs.json_safe({"f": object()})


# ---------------------------------------------------------------------------
# Spans / tracer
# ---------------------------------------------------------------------------

def test_span_nesting_builds_tree_and_ring_keeps_roots_only():
    tr = obs.Tracer(max_traces=8)
    with tr.span("flush", queued=2):
        with tr.span("bucket", route="histogram", bucket=2):
            with tr.span("solve"):
                pass
            with tr.span("materialize"):
                pass
    traces = tr.traces()
    assert len(traces) == 1                  # only the root lands
    root = traces[0]
    assert root["name"] == "flush" and root["attrs"] == {"queued": 2}
    (bucket,) = root["children"]
    assert [c["name"] for c in bucket["children"]] == ["solve",
                                                       "materialize"]
    assert all(c["wall_s"] >= 0.0 for c in bucket["children"])
    json.dumps(traces)                       # trace records are plain JSON


def test_span_exception_marks_error_and_propagates():
    tr = obs.Tracer()
    with pytest.raises(ValueError, match="boom"):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("boom")
    assert tr.current_span is None           # stack fully unwound
    root = tr.traces()[-1]
    assert root["status"] == "error" and "boom" in root["error"]
    inner = root["children"][0]
    assert inner["status"] == "error" and inner["wall_s"] is not None
    with tr.span("after"):                   # tracer still usable
        pass
    assert tr.traces()[-1]["name"] == "after"


def test_disabled_tracer_times_but_records_nothing():
    tr = obs.Tracer(enabled=False)
    with tr.span("solve") as sp:
        pass
    assert sp.wall_s is not None             # timing still works
    assert tr.traces() == []


def _profiled(fn, log_dir):
    """Run ``fn`` under the JAX profiler; returns the host events named
    ``fcm.*`` as (name, start_ns, end_ns, stats) in start order."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fcm."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


def test_ring_false_skips_ring_but_feeds_metrics(tmp_path):
    """A ring=False span stays out of the ring but still reaches the
    profiler trace as an ``fcm.<name>`` annotation (without attrs it was
    not asked to carry)."""
    tr = obs.Tracer()

    def body():
        with tr.span("ingest", ring=False, route="histogram") as sp:
            pass
        assert sp.wall_s is not None

    events = _profiled(body, tmp_path)
    assert tr.traces() == []
    assert [(e[0], e[3]) for e in events] == [("fcm.ingest", {})]


def test_ring_buffer_caps_at_max_traces():
    tr = obs.Tracer(max_traces=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert [t["name"] for t in tr.traces()] == ["s2", "s3", "s4"]
    tr.clear()
    assert tr.traces() == []


def test_span_fence_records_device_time():
    import jax.numpy as jnp
    tr = obs.Tracer()
    with tr.span("launch") as sp:
        out = sp.fence(jnp.arange(8) * 2)
    assert int(out[3]) == 6
    assert sp.device_s is not None and sp.device_s <= sp.wall_s


# ---------------------------------------------------------------------------
# Engine spans in the profiler, stage and queue-wait counters
# ---------------------------------------------------------------------------

def _engine(**kw):
    from repro.serving.fcm_engine import FCMServeEngine
    return FCMServeEngine(F.FCMConfig(max_iters=300), cache_size=0, **kw)


def _slices(n, h=32, w=32):
    return [phantom.phantom_slice(h, w, noise=2.0 + i, seed=i)[0]
            for i in range(n)]


def _stage(eng, route, stage):
    return eng.metrics.counter("route.stage_seconds", route=route,
                               stage=stage).value


def test_flush_spans_nest_in_profiler_trace(tmp_path):
    """One traced flush leaves fcm.flush > fcm.bucket > (fcm.gather,
    fcm.launch > fcm.h2d, fcm.scatter > fcm.d2h) as host events, in
    that order, with tracing off (the annotations do not need the
    ring); route, bucket and shards (1: no mesh) ride on fcm.bucket
    alone."""
    eng = _engine(batch_sizes=(4,), tracing=False)
    imgs = _slices(4)
    eng.segment(imgs)                        # compile outside the trace

    events = _profiled(lambda: eng.segment(imgs), tmp_path)
    by = {}
    for e in events:
        by.setdefault(e[0], []).append(e)
    assert sorted(by) == ["fcm.bucket", "fcm.d2h", "fcm.flush",
                          "fcm.gather", "fcm.h2d", "fcm.ingest",
                          "fcm.launch", "fcm.scatter"]
    assert len(by["fcm.ingest"]) == 4
    for name in set(by) - {"fcm.ingest"}:
        assert len(by[name]) == 1, name
    one = {k: v[0] for k, v in by.items()}

    def inside(child, parent):
        c, p = one[child], one[parent]
        return p[1] <= c[1] and c[2] <= p[2]

    assert inside("fcm.bucket", "fcm.flush")
    for stage in ("fcm.gather", "fcm.launch", "fcm.scatter"):
        assert inside(stage, "fcm.bucket")
    assert inside("fcm.h2d", "fcm.launch")
    assert inside("fcm.d2h", "fcm.scatter")
    assert (one["fcm.gather"][2] <= one["fcm.launch"][1]
            and one["fcm.launch"][2] <= one["fcm.scatter"][1])
    assert one["fcm.bucket"][3] == {"route": "histogram", "bucket": 4,
                                    "shards": 1}
    for name, ev in one.items():
        if name != "fcm.bucket":
            assert ev[3] == {}, name         # no request ids, no attrs
    assert eng.tracer.traces() == []


def test_submit_without_flush_moves_ingest_not_gather():
    eng = _engine(batch_sizes=(4,))
    for img in _slices(3):
        eng.submit(img)
    assert _stage(eng, "histogram", "ingest") > 0
    assert _stage(eng, "histogram", "gather") == 0
    assert eng.stats()["stage_seconds"]["histogram"]["gather"] == 0
    eng.flush()
    assert _stage(eng, "histogram", "gather") > 0
    assert eng.stats()["stage_seconds"]["histogram"]["gather"] > 0


@pytest.mark.parametrize("route", ["histogram", "spatial"])
def test_transfers_sit_inside_their_stages(route):
    """h2d is a child of the fenced launch and d2h of the scatter, so
    their counters never exceed solve and materialize."""
    eng = _engine(batch_sizes=(4,))
    eng.segment(_slices(3), method=route)
    h2d, d2h = _stage(eng, route, "h2d"), _stage(eng, route, "d2h")
    assert 0 < h2d <= _stage(eng, route, "solve")
    assert 0 < d2h <= _stage(eng, route, "materialize")


def test_queue_wait_counts_submit_to_gather():
    """A planted 50 ms between submit and flush shows in every lane's
    queue wait: route.queue_wait_seconds >= 0.05 x n."""
    import time

    eng = _engine(batch_sizes=(4,))
    imgs = _slices(3)
    eng.segment(imgs)                        # compile outside the wait
    wait = eng.metrics.counter("route.queue_wait_seconds",
                               route="histogram")
    before = wait.value
    for img in imgs:
        eng.submit(img)
    time.sleep(0.05)
    eng.flush()
    assert wait.value - before >= 0.05 * len(imgs)


# ---------------------------------------------------------------------------
# Solver convergence telemetry
# ---------------------------------------------------------------------------

CFG = F.FCMConfig(max_iters=300)


def test_solve_records_iters_and_residual():
    reg = obs.default_registry()
    reg.reset()
    img = phantom.phantom_slice(48, 48, noise=3.0, seed=0)[0]
    res = SV.solve(SV.histogram_problem(img.ravel().astype(np.float32),
                                        CFG), CFG)
    h = reg.peek("solver.iters", kind="flat")
    assert h is not None and h.count == 1
    assert h.quantile(0.5) == pytest.approx(res.n_iters, abs=1.0)
    g = reg.peek("solver.last_final_delta", kind="flat")
    assert g is not None and g.value == pytest.approx(res.final_delta)


def test_batched_telemetry_matches_per_lane_iters_on_ragged_batch():
    """Per-lane masked iteration counts land in the histogram: on a
    ragged batch the recorded lane iters must equal the result's
    n_iters lane for lane — not B copies of the shared trip count."""
    reg = obs.default_registry()
    reg.reset()
    imgs = [phantom.phantom_slice(40 + 8 * i, 64, noise=2.0 + 3 * i,
                                  seed=i)[0] for i in range(3)]
    from repro.core import batched as B
    hists = B.histograms_of(imgs)
    batch = SV.batch_problems(B.hist_rows(hists), hists, cfg=CFG)
    res = SV.solve_batched(batch, CFG)
    lane_iters = np.asarray(res.n_iters)
    assert len(set(lane_iters.tolist())) > 1  # genuinely ragged
    h = reg.peek("solver.iters", kind="flat")
    assert h.count == 3
    assert h.total == pytest.approx(float(lane_iters.sum()))
    assert h.vmin == float(lane_iters.min())
    assert h.vmax == float(lane_iters.max())
    assert reg.peek("solver.lanes", kind="flat",
                    impl="reference").value == 3
    assert reg.peek("solver.solves", kind="flat",
                    impl="reference").value == 1
    g = reg.peek("solver.last_final_delta", kind="flat")
    assert g.value == pytest.approx(float(np.max(res.final_delta)))


# ---------------------------------------------------------------------------
# Scoped registries (the sweep harness's per-cell capture)
# ---------------------------------------------------------------------------

def test_scoped_registry_captures_without_touching_default():
    base = obs.default_registry()
    base.reset()
    with obs.scoped_registry() as reg:
        assert obs.default_registry() is reg
        assert reg is not base
        obs.default_registry().counter("inner").inc(3)
    assert obs.default_registry() is base
    assert reg.peek("inner").value == 3
    assert base.peek("inner") is None


def test_scoped_registry_nests_innermost_wins():
    with obs.scoped_registry() as outer:
        outer_active = obs.default_registry()
        with obs.scoped_registry() as inner:
            obs.default_registry().counter("n").inc()
        assert obs.default_registry() is outer_active is outer
        assert inner.peek("n").value == 1
        assert outer.peek("n") is None


def test_scoped_registry_accepts_caller_registry():
    mine = obs.MetricsRegistry()
    with obs.scoped_registry(mine) as reg:
        assert reg is mine
        obs.default_registry().gauge("g").set(2.5)
    assert mine.peek("g").value == 2.5


def test_scoped_registry_pops_on_exception():
    base = obs.default_registry()
    with pytest.raises(RuntimeError):
        with obs.scoped_registry():
            raise RuntimeError("boom")
    assert obs.default_registry() is base


def test_scoped_registry_captures_solver_telemetry():
    base = obs.default_registry()
    base.reset()
    img = phantom.phantom_slice(32, 32, noise=3.0, seed=0)[0]
    prob = SV.histogram_problem(img.ravel().astype(np.float32), CFG)
    with obs.scoped_registry() as reg:
        res = SV.solve(prob, CFG)
    h = reg.peek("solver.iters", kind="flat")
    assert h is not None and h.count == 1
    assert h.vmax == float(res.n_iters)
    # nothing leaked into the process-wide registry (reset() keeps the
    # key registered from earlier tests, so check the count, not None)
    leaked = base.peek("solver.iters", kind="flat")
    assert leaked is None or leaked.count == 0
