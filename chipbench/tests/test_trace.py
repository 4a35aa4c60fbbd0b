"""The trace reduction on a small trace recorded on a TPU v5e by
``record_trace.py``: three ``request`` calls of a matmul chain inside one
``window``, each after 50 ms of host sleep."""
import dataclasses
import os

import pytest

from chipbench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return tr.load(DATA)


def _raw_device_events():
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(DATA).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
    return out


def test_one_device_and_the_harness_annotations(small):
    assert list(small.devices) == ["/device:TPU:0"]
    names = [n for n, _, _ in small.annotations]
    assert names.count("window") == 1 and names.count("request") == 3
    w0, w1 = small.window
    assert all(w0 <= s <= e <= w1 for n, s, e in small.annotations
               if n == "request")
    # three sleeps of 50 ms at least
    assert small.window_s > 0.15


def test_busy_time_is_the_union_of_device_operations(small):
    ev = _raw_device_events()
    assert ev
    # the union, by a sweep over the sorted event edges
    w0, w1 = small.window
    edges = sorted([(max(s, w0), 1) for s, e, _ in ev if e > w0 and s < w1]
                   + [(min(e, w1), -1) for s, e, _ in ev if e > w0 and s < w1])
    busy, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert small.busy_s() == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0 < small.busy_s() < small.window_s


def test_device_time_inside_each_request(small):
    # On this trace the device's clock runs up to ~1 ms ahead of the
    # host's: each request's operations begin before its host span does.
    # The cells' annotated spans last 0.25 s or more, so the shift moves
    # little there; here each request is read with 2 ms of lead.
    lead = 2e6
    spans = small.spans("request")
    per = [small.busy_s(s - lead, e) for s, e in spans]
    assert all(p > 0 for p in per)
    # requests hold all the device work of the window
    assert sum(per) == pytest.approx(small.busy_s(), rel=1e-6)
    # the gaps inside a request are short hand-offs, not the host's sleep
    assert all(g < 0.05e9 for s, e in spans for g in small.gaps(s - lead, e))


def test_longest_idle_gaps_are_the_host_sleeps(small):
    gaps = small.named_gaps(10)
    assert len(gaps) <= 10
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    top3 = gaps[:3]
    assert all(name == "window" and s >= 0.05 for name, s in top3)


def test_top_operations(small):
    ops = small.top_ops(10)
    ev = _raw_device_events()
    total = {}
    for s, e, n in ev:
        total[n] = total.get(n, 0) + (e - s)
    name, secs = ops[0]
    assert secs == pytest.approx(max(total.values()) * 1e-9)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)


def test_serve_gap_reads_the_hand_offs(small):
    from chipbench.harness import Run, reader

    # the requests read with the 2 ms lead of the device's clock (above)
    led = dataclasses.replace(small, annotations=[
        (n, s - 2e6 if n == "request" else s, e)
        for n, s, e in small.annotations])
    run = Run(config={}, traffic={}, trace=led)
    gap_ms = reader("serve.gap_ms")(run)
    # between the four calls of each request the host hands off in tens of
    # microseconds; the nanosecond seams inside one program do not count
    assert 0.01 <= gap_ms < 1.0
