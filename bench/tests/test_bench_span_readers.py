"""The readers of the engine's and the frontend's spans and counters,
fed a hand-made window: each gives its value where the program recorded
something, and None where it recorded nothing (a program without the
span or counter)."""
import pytest

from bench import harness


def _hist(count, total):
    return {"unit": "s", "count": count, "sum": total, "buckets": []}


EMPTY = {"counters": {}, "gauges": {}, "histograms": {}}

CASES = [
    # metric, registry at the window's start, at its end, value
    ("frontend.queue_wait_ms.open",
     {"histograms": {"serve.frontend.queue_wait_ms": _hist(10, 50.0)}},
     {"histograms": {"serve.frontend.queue_wait_ms": _hist(14, 70.0)}},
     5.0),
    ("engine.frontier_sync_ms.open",
     {"histograms": {"span.engine.frontier_sync": _hist(2, 0.01)}},
     {"histograms": {"span.engine.frontier_sync": _hist(6, 0.05)}},
     10.0),
    ("engine.certificate_ms.open",
     {"histograms": {"span.engine.certificate": _hist(1, 0.002)}},
     {"histograms": {"span.engine.certificate": _hist(5, 0.010)}},
     2.0),
    ("engine.phase2_fill_pct.open",
     {"counters": {"engine.phase2_slots{kind=frontier}": 100,
                   "engine.phase2_slots{kind=evaluated}": 1000}},
     {"counters": {"engine.phase2_slots{kind=frontier}": 400,
                   "engine.phase2_slots{kind=evaluated}": 2000}},
     30.0),
    # not a window delta: everything built since the process started
    ("engine.jit_build_s.open",
     {"histograms": {"jit.compile_s{fun=b}": _hist(1, 1.0)}},
     {"histograms": {"jit.trace_s{fun=a}": _hist(3, 1.5),
                     "jit.lower_s{fun=a}": _hist(1, 0.5),
                     "jit.compile_s{fun=b}": _hist(2, 2.0),
                     "span.engine.dispatch": _hist(9, 9.0)}},
     4.0),
]


def _ctx(obs0, obs1):
    c = harness.cell("road3d.open")
    return harness._Context(c, {**EMPTY, **obs0}, {**EMPTY, **obs1},
                            trace=None, device={})


@pytest.mark.parametrize("metric,obs0,obs1,value", CASES,
                         ids=[case[0] for case in CASES])
def test_span_reader(metric, obs0, obs1, value):
    c = harness.cell("road3d.open")
    assert metric in [m["name"] for m in c.per_layer]
    read = c.reader(metric)
    assert read(_ctx(obs0, obs1)) == pytest.approx(value)
    # nothing recorded, or nothing new in the window: no reading
    assert read(_ctx({}, {})) is None
    if metric != "engine.jit_build_s.open":
        assert read(_ctx(obs1, obs1)) is None
