"""Async admission: futures, deadlines, batch-formation policy,
shutdown semantics — and bitwise parity between the async front door
and the synchronous submit/flush path (same RouteProgram, same math)."""
import threading
import time

import numpy as np
import pytest

from repro.core import fcm as F
from repro.data import phantom
from repro.serving.admission import (DeadlineExceeded, EngineShutdown,
                                     SegmentationFuture)
from repro.serving.fcm_engine import FCMServeEngine

CFG = F.FCMConfig(max_iters=300)


def _imgs(n, size=24):
    return [phantom.phantom_slice(size, size, noise=4.0 + (i % 3),
                                  seed=100 + i)[0] for i in range(n)]


def _engine(**kw):
    kw.setdefault("cache_size", 0)
    kw.setdefault("batch_sizes", (1, 4))
    return FCMServeEngine(CFG, **kw)


# -- SegmentationFuture ------------------------------------------------------

def test_future_resolves_exactly_once():
    fut = SegmentationFuture(0, "histogram")
    assert not fut.done() and fut.latency_s is None
    fut.set_result("r")
    assert fut.done() and fut.result() == "r"
    assert fut.latency_s is not None and fut.latency_s >= 0
    with pytest.raises(RuntimeError, match="resolved twice"):
        fut.set_result("again")
    with pytest.raises(RuntimeError, match="resolved twice"):
        fut.set_exception(ValueError("nope"))


def test_future_timeout_and_exception():
    fut = SegmentationFuture(1, "histogram")
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)
    fut.set_exception(ValueError("boom"))
    with pytest.raises(ValueError, match="boom"):
        fut.result()
    assert isinstance(fut.exception(), ValueError)


# -- drain / parity ----------------------------------------------------------

def test_zero_request_drain_is_noop():
    eng = _engine()
    assert eng.drain() == []
    assert eng.drain() == []          # repeatable
    eng.shutdown()


def test_async_bitwise_identical_to_sync():
    imgs = _imgs(6)
    sync_eng = _engine()
    for im in imgs:
        sync_eng.submit(im)
    sync_res = {r.request_id: r for r in sync_eng.flush()}
    sync_eng.shutdown()

    async_eng = _engine(max_wait_ms=10_000.0)   # only drain() flushes
    futs = [async_eng.submit_async(im) for im in imgs]
    async_eng.drain()
    for i, fut in enumerate(futs):
        a, s = fut.result(timeout=5), sync_res[i]
        assert (a.labels == s.labels).all()
        np.testing.assert_array_equal(a.centers, s.centers)
        assert a.n_iters == s.n_iters
    async_eng.shutdown()


def test_exactly_once_with_duplicates_and_cache_hits():
    # Duplicate payloads dedup within a flush and hit the LRU across
    # flushes; every future must still resolve exactly once, with the
    # representative's centers.
    eng = _engine(cache_size=64, max_wait_ms=10_000.0)
    img = _imgs(1)[0]
    futs = [eng.submit_async(img) for _ in range(3)]
    eng.drain()
    first = [f.result(timeout=5) for f in futs]
    assert all(f.done() for f in futs)
    # Across-flush cache hit: new request, same histogram.
    fut2 = eng.submit_async(img.copy())
    eng.drain()
    again = fut2.result(timeout=5)
    assert again.cache_hit
    np.testing.assert_array_equal(again.centers, first[0].centers)
    assert (again.labels == first[0].labels).all()
    eng.shutdown()


# -- deadlines ---------------------------------------------------------------

def test_expired_deadline_at_submit_consumes_nothing():
    eng = _engine()
    before = eng._next_id
    fut = eng.submit_async(_imgs(1)[0], deadline=0.0)
    assert fut.done()
    with pytest.raises(DeadlineExceeded):
        fut.result()
    assert eng._next_id == before             # no id, no queue slot
    assert eng.drain() == []
    assert eng._route_counter("deadline_expired", "histogram").value == 1
    eng.shutdown()


def test_deadline_expired_while_queued():
    eng = _engine(max_wait_ms=10_000.0)
    imgs = _imgs(2)
    doomed = eng.submit_async(imgs[0], deadline=0.005)
    ok = eng.submit_async(imgs[1])
    time.sleep(0.02)
    eng.drain()
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=5)
    res = ok.result(timeout=5)                # batchmate unaffected
    assert res.labels.shape == imgs[1].shape
    eng.shutdown()


def test_deadline_ordering_most_urgent_first():
    # _admit_order sorts a drained queue by absolute deadline so tight
    # deadlines land in the earliest chunk of their bucket group.
    eng = _engine(max_wait_ms=10_000.0)
    imgs = _imgs(3)
    loose = eng.submit_async(imgs[0], deadline=60.0)
    none = eng.submit_async(imgs[1])
    tight = eng.submit_async(imgs[2], deadline=5.0)
    with eng._lock:
        pend = list(eng._queues["histogram"])
    from repro.serving.fcm_engine import ROUTES
    ordered = eng._admit_order(ROUTES["histogram"], pend)
    assert [p.request_id for p in ordered] == [
        tight.request_id, loose.request_id, none.request_id]
    # The reordered queue still resolves everyone (ids stay attached).
    eng.drain()
    for f in (loose, none, tight):
        assert f.result(timeout=5).labels.shape == imgs[0].shape
    eng.shutdown()


# -- background flusher ------------------------------------------------------

def test_flusher_is_lazy_and_sync_api_never_starts_it():
    eng = _engine()
    eng.submit(_imgs(1)[0])
    eng.flush()
    assert eng._flusher is None
    eng.submit_async(_imgs(1)[0])
    assert eng._flusher is not None and eng._flusher.is_alive()
    eng.shutdown()


def test_max_wait_flush_without_explicit_drain():
    eng = _engine(max_wait_ms=20.0)
    fut = eng.submit_async(_imgs(1)[0])
    res = fut.result(timeout=10)              # background flusher only
    assert res.labels.shape == (24, 24)
    assert fut.latency_s >= 0.015             # waited out the window
    eng.shutdown()


def test_target_shape_triggers_before_window():
    # A full target-shape group flushes immediately, long before the
    # (deliberately huge) admission window.
    eng = _engine(batch_sizes=(1, 2), max_wait_ms=60_000.0)
    imgs = _imgs(2)
    futs = [eng.submit_async(im) for im in imgs]
    for f in futs:
        assert f.result(timeout=10).labels.shape == imgs[0].shape
    assert max(f.latency_s for f in futs) < 30.0
    eng.shutdown()


def test_concurrent_submitters_all_resolve():
    eng = _engine(batch_sizes=(1, 8), max_wait_ms=15.0)
    imgs = _imgs(12)
    out = {}

    def worker(i):
        out[i] = eng.submit_async(imgs[i]).result(timeout=30)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(imgs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(out) == list(range(12))
    for i, r in out.items():
        assert r.labels.shape == imgs[i].shape
    eng.shutdown()


# -- shutdown ----------------------------------------------------------------

def test_shutdown_drains_in_flight_futures():
    eng = _engine(max_wait_ms=10_000.0)
    futs = [eng.submit_async(im) for im in _imgs(3)]
    eng.shutdown()                            # drain=True default
    for f in futs:
        assert f.result(timeout=5).labels.shape == (24, 24)
    with pytest.raises(EngineShutdown):
        eng.submit_async(_imgs(1)[0])
    with pytest.raises(EngineShutdown):
        eng.submit(_imgs(1)[0])
    eng.shutdown()                            # idempotent


def test_concurrent_shutdown_and_erroring_route_exactly_once():
    # Regression: a route whose solve raises, racing shutdown(drain=True)
    # — both paths try to resolve the same futures. Every future must
    # resolve exactly once (typed error or EngineShutdown), with no
    # "resolved twice" RuntimeError escaping either resolver and no
    # future left pending.
    import dataclasses

    from repro import faults as FI

    plan = FI.FaultPlan(seed=0, specs=(
        FI.FaultSpec(site="launch", kind="error", times=None),))
    eng = _engine(faults=plan, retries=0, breaker_threshold=10**9,
                  max_wait_ms=10_000.0)
    futs = [eng.submit_async(im) for im in _imgs(4)]

    def boom(*a, **k):
        raise ValueError("solver exploded")

    reference_program_for = eng._reference_program_for
    # The degraded chunk's reference launch raises too.
    eng._reference_program_for = lambda *a: dataclasses.replace(
        reference_program_for(*a), launch=boom)
    errs = []

    def flusher():
        try:
            eng.flush(raise_errors=False)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=flusher)
    t.start()
    eng.shutdown(drain=True)
    t.join()
    assert errs == []                       # no "resolved twice" escaped
    for f in futs:
        assert f.done()
        assert isinstance(f.exception(), (ValueError, EngineShutdown))
    assert eng.stats()["pending_futures"] == 0
    eng.shutdown()


def test_shutdown_drop_fails_queued_futures():
    eng = _engine(max_wait_ms=10_000.0)
    futs = [eng.submit_async(im) for im in _imgs(2)]
    eng.shutdown(drain=False)
    for f in futs:
        with pytest.raises(EngineShutdown):
            f.result(timeout=5)
    assert eng.closed
    assert eng.metrics.gauge("queue.depth").value == 0
