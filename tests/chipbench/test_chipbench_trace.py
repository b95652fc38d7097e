"""The profiler reduction on a small trace whose numbers are worked out
by hand (tests/chipbench/data/trace_small.json)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "trace_small.json"


def _load(path):
    def ns(d):
        if isinstance(d, dict):
            return SimpleNamespace(**{k: ns(v) for k, v in d.items()})
        if isinstance(d, list):
            return [ns(x) for x in d]
        return d
    return ns(json.loads(path.read_text()))


def test_reduction_of_the_small_trace():
    s = trace.reduce(_load(DATA).planes, host_window=(1.0, 2.0))
    # window: the benchmark's spans, 0 .. 10000 ns
    assert s.window_s == pytest.approx(10_000e-9)
    # TPU:0 busy = [1500,4000) + [4200,4300) + [5000,8000) + [8500,9500)
    # = 6600 ns (fusion.1 and fusion.2 overlap; the op at 12000 is
    # outside); TPU:1 busy = [2000,6000) = 4000 ns; TPU:2 ran nothing in
    # the window and does not count
    assert s.busy_s == pytest.approx((6600 + 4000) / 2 * 1e-9)
    # modules starting inside the window: prefill 2500, decode
    # 3000 + 1000 (TPU:0) + 4000 (TPU:1)
    assert s.program_s["prefill"] == pytest.approx(2500e-9)
    assert s.program_s["decode"] == pytest.approx(8000e-9)
    # self time inside the window, both chips, longest first, named by
    # the program it ran in ("?" for copy.5, which no module covers) and
    # by the part of its HLO text before " = "; fusion.1 loses the 500 ns
    # that fusion.2 overlaps, so the times add up to the busy time
    assert [(n, round(t * 1e9)) for n, t in s.top_ops] == [
        ("jit_decode_fn/fusion.9", 4000),
        ("jit_decode_fn/convolution.3", 3000),
        ("jit_prefill_fn/fusion.2", 1500),
        ("jit_prefill_fn/fusion.1", 1000),
        ("jit_decode_fn/fusion.1", 1000),
        ("?/copy.5", 100)]
    assert sum(t for _, t in s.top_ops) == pytest.approx(2 * s.busy_s)
    # gaps, TPU:0 first: [0,1500) (middle 750, in wave_submit),
    # [4000,4200), [4300,5000), [8000,8500) (wave_serve), [9500,10000)
    # (wave_collect); TPU:1: [0,2000) (middle 1000: wave_serve starts
    # there) and [6000,10000) (wave_serve); longest first
    assert [(n, round(t * 1e9)) for n, t in s.idle_gaps] == [
        ("wave_serve", 4000), ("wave_serve", 2000), ("wave_submit", 1500),
        ("wave_serve", 700), ("wave_serve", 500), ("wave_collect", 500),
        ("wave_serve", 200)]
    assert s.host_window == (1.0, 2.0)
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_benchmark_span_or_no_device_op_gives_nothing():
    planes = _load(DATA).planes
    assert trace.reduce([p for p in planes if p.name != "/host:CPU"],
                        (0.0, 1.0)) is None
    assert trace.reduce([p for p in planes
                         if not p.name.startswith("/device")],
                        (0.0, 1.0)) is None


def test_self_time_leaves_out_nested_operations():
    ops = [(0, 100, "loop"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c"),
           (120, 130, "a")]
    assert sorted(trace.self_times(ops)) == sorted([
        ("loop", 100 - 20 - 50), ("a", 20), ("b", 50 - 10), ("c", 10),
        ("a", 10)])


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert trace.clip(busy, 1, 6) == [(1, 3), (5, 6)]


def test_reduction_of_a_recorded_v5e_slice():
    """A slice recorded on the chip: the reduction's busy time, op times
    and gaps equal a count, nanosecond by nanosecond, of the same
    events."""
    import numpy as np
    planes = _load(DATA.parent / "trace_v5e.json").planes
    s = trace.reduce(planes, host_window=(0.0, 1.0))
    ops = planes[0].lines[0].events
    spans = planes[1].lines[0].events
    lo = min(e.start_ns for e in spans)
    hi = max(e.start_ns + e.duration_ns for e in spans)
    first = min(e.start_ns for e in ops)
    last = max(e.start_ns + e.duration_ns for e in ops)
    mask = np.zeros(last - first, bool)
    for e in ops:
        mask[e.start_ns - first:e.start_ns - first + e.duration_ns] = True
    assert round(s.busy_s * 1e9) == int(mask.sum())
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.program_s == {"prefill": 0.0, "decode": 0.0}   # no module
    assert sum(t for _, t in s.top_ops) <= s.busy_s + 1e-12
    assert all(n.startswith("?/%") and " " not in n for n, _ in s.top_ops)
    # the longest gaps: after the slice's last op and before its first,
    # both inside the serve call
    assert s.idle_gaps[0] == ("wave_serve", pytest.approx((hi - last) * 1e-9))
    assert s.idle_gaps[1] == ("wave_serve",
                              pytest.approx((first - lo) * 1e-9))
