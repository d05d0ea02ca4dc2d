"""The split of device idle time by engine span, against numbers worked
out by hand."""
import pytest

from bench.lib import span_idle as SI
from bench.lib import trace as TR


def test_idle_by_span_by_hand():
    # Device 0 busy [0,150) [200,250) [400,500) over the traced span
    #   [0,1000): idle [150,200) [250,400) [500,1000). Device 1 is busy
    #   throughout, so every share halves.
    # Engine spans: flush [100,600) > bucket [120,580) > gather [120,160),
    #   launch [160,300) > h2d [160,180), scatter [300,450) > d2h
    #   [300,320); fcm.ingest (a client thread) overlaps and labels
    #   nothing.
    #   [150,200): gather 10, h2d 20, launch 20 (one gap, three spans)
    #   [250,400): launch 50, d2h 20, scatter 80
    #   [500,1000): bucket 80, flush 20, outside any flush 400
    t = TR.Trace(
        {0: [("a\t", 0.0, 150.0), ("b\t", 200.0, 50.0), ("c\t", 400.0, 100.0)],
         1: [("d\t", 0.0, 1000.0)]},
        [("thread", 0.0, 1000.0), ("fcm.ingest", 140.0, 760.0),
         ("fcm.flush", 100.0, 500.0), ("fcm.bucket", 120.0, 460.0),
         ("fcm.gather", 120.0, 40.0), ("fcm.launch", 160.0, 140.0),
         ("fcm.h2d", 160.0, 20.0), ("fcm.scatter", 300.0, 150.0),
         ("fcm.d2h", 300.0, 20.0)])
    got = SI.idle_by_span(t)
    want = {"fcm.gather": 5, "fcm.h2d": 10, "fcm.launch": 35,
            "fcm.d2h": 10, "fcm.scatter": 40, "fcm.bucket": 40,
            "fcm.flush": 10, SI.OUTSIDE_FLUSH: 200}
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v * 1e-9), k
    busy = (TR.busy_ns(t.devices[0]) + TR.busy_ns(t.devices[1])) / 2
    assert sum(got.values()) == pytest.approx((1000 - busy) * 1e-9)


def test_idle_by_span_without_engine_spans_is_empty():
    t = TR.Trace({0: [("a\t", 0.0, 10.0)]},
                 [("thread", 0.0, 100.0), ("fcm.ingest", 20.0, 5.0)])
    assert SI.idle_by_span(t) == {}
    assert SI.idle_by_span(TR.Trace({}, [("fcm.flush", 0.0, 5.0)])) == {}
