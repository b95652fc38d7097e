"""Tests for repro.telemetry: sharded registry merge semantics, span
tracer determinism, exporters, the always-on component wiring, and the
torn-snapshot fixes in throughput/overheads introspection.

The hypothesis-based shard-merge properties live in
tests/test_telemetry_properties.py (skipped when hypothesis is absent);
everything here is deterministic and runs in the fast suite.
"""
import json
import threading
import time

import pytest

from repro import telemetry as telemetry_mod
from repro.core import (ChunkRecord, DeviceKind, DynamicScheduler,
                        GroupSpec, SleepExecutor)
from repro.core.overheads import OverheadLedger
from repro.core.throughput import ThroughputTracker
from repro.core.types import Chunk, Token
from repro.queue import Job, JobService
from repro.telemetry import (MetricsExporter, MetricsRegistry, OFF,
                             SpanTracer, Telemetry, prometheus_text,
                             read_jsonl, resolve)


# ---------------------------------------------------------------------------
# registry: sharded merge semantics
# ---------------------------------------------------------------------------

def _in_threads(n, fn):
    threads = [threading.Thread(target=fn, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def test_counter_merges_across_thread_shards():
    reg = MetricsRegistry()
    c = reg.counter("hits")

    def work(i):
        for _ in range(1000):
            c.add(1)

    _in_threads(4, work)
    assert c.value() == 4000
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 4000


def test_counter_labels_are_distinct_series():
    reg = MetricsRegistry()
    reg.counter("jobs", tenant="a").add(2)
    reg.counter("jobs", tenant="b").add(3)
    snap = reg.snapshot()["counters"]
    assert snap['jobs{tenant="a"}'] == 2
    assert snap['jobs{tenant="b"}'] == 3


def test_gauge_last_write_wins_across_threads():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(1.0)

    def work(i):
        g.set(10.0 + i)

    _in_threads(2, work)
    g.set(99.0)                      # highest global sequence number
    assert g.value() == 99.0


def test_histogram_merge_equals_single_shard_ingest():
    values = [0.00001 * (i + 1) for i in range(400)] + [0.0, -1.0, 5.0]
    ref = MetricsRegistry().histogram("ref")
    for v in values:
        ref.observe(v)

    sharded = MetricsRegistry().histogram("sharded")
    quarters = [values[i::4] for i in range(4)]

    def work(i):
        for v in quarters[i]:
            sharded.observe(v)

    _in_threads(4, work)
    a, b = ref.merged(), sharded.merged()
    assert a["buckets"] == b["buckets"]
    assert a["count"] == b["count"] == len(values)
    assert a["min"] == b["min"] and a["max"] == b["max"]
    assert a["sum"] == pytest.approx(b["sum"])


def test_histogram_quantile_error_bound():
    # log-bucketed with growth 2**0.25: a quantile comes back as its
    # bucket's upper bound, within 2**0.25 - 1 (~19%) above the true value
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    values = [1e-6 * (1.19 ** i) for i in range(200)]
    for v in values:
        h.observe(v)
    for q in (0.5, 0.95, 0.99):
        true = sorted(values)[int(q * (len(values) - 1))]
        est = h.quantile(q)
        assert true <= est * 1.0000001
        assert est <= true * (2 ** 0.25) * 1.0000001
    # quantiles clamp to observed extremes
    assert h.quantile(0.0) >= min(values)
    assert h.quantile(1.0) <= max(values)


def test_histogram_nonpositive_values_bucketed():
    reg = MetricsRegistry()
    h = reg.histogram("x")
    h.observe(0.0)
    h.observe(-3.0)
    h.observe(1.0)
    m = h.merged()
    assert m["count"] == 3 and m["min"] == -3.0
    text = prometheus_text(reg)
    assert 'le="0"' in text and "x_count 3" in text


def test_snapshot_is_self_measuring():
    reg = MetricsRegistry()
    c = reg.counter("n")
    for _ in range(100):
        c.add(1)
    snap = reg.snapshot()
    self_ = snap["self"]
    assert self_["ops"] >= 100
    assert self_["ns_per_op"] > 0
    assert self_["est_overhead_s"] >= 0.0
    assert self_["snapshots"] == 1


def test_collectors_run_at_snapshot_and_prune_dead():
    reg = MetricsRegistry()

    class Src:
        def collect(self):
            reg.gauge("live").set(7.0)

    src = Src()
    reg.add_collector(src.collect)
    assert reg.snapshot()["gauges"]["live"] == 7.0
    del src
    reg.snapshot()                   # dead weakref pruned, no error


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def _record(group="g0", seq=0, size=8, base=100.0):
    rec = ChunkRecord(token=Token(Chunk(0, size, seq), group,
                                  DeviceKind.BIG))
    rec.tc1 = base
    rec.tc2 = base + 0.001
    rec.tg1 = base + 0.002
    rec.tg2 = base + 0.003
    rec.tg3 = base + 0.004
    rec.tg4 = base + 0.005
    rec.tg5 = base + 0.006
    rec.tc3 = base + 0.007
    return rec


def test_sampling_is_deterministic_by_seq():
    a = SpanTracer(sample_rate=0.5)
    b = SpanTracer(sample_rate=0.5)
    picks_a = [a.sampled(i) for i in range(1000)]
    picks_b = [b.sampled(i) for i in range(1000)]
    assert picks_a == picks_b
    assert 300 < sum(picks_a) < 700          # roughly the requested rate
    assert all(SpanTracer(sample_rate=1.0).sampled(i) for i in range(50))
    assert not any(SpanTracer(sample_rate=0.0).sampled(i)
                   for i in range(50))


def test_tracer_ring_is_bounded_and_counts_drops():
    tr = SpanTracer(max_events=10)
    for i in range(25):
        tr.instant("e", ts=float(i))
    assert len(tr) == 10
    assert tr.emitted == 25 and tr.dropped == 15


def test_epoch_tags_attach_to_chunk_spans():
    tr = SpanTracer()
    tr.tag_epoch(3, {"tenants": {"gold": 8}})
    tr.chunk(_record(seq=1), epoch=3)
    ev = [e for e in tr.chrome_events() if e.get("cat") == "chunk"]
    assert len(ev) == 1
    assert ev[0]["args"]["tenants"] == {"gold": 8}
    assert ev[0]["args"]["epoch"] == 3


def test_epoch_tag_map_is_bounded():
    tr = SpanTracer(max_epoch_tags=100)
    for i in range(500):
        tr.tag_epoch(i, {"i": i})
    assert len(tr._epoch_tags) == 100
    assert tr.epoch_tag(499) == {"i": 499}   # newest kept
    assert tr.epoch_tag(0) == {}             # oldest evicted


def test_chrome_trace_structure_and_nesting():
    tr = SpanTracer()
    for i in range(3):
        tr.chunk(_record(seq=i, base=100.0 + i), epoch=0)
    trace = tr.chrome_trace()
    evs = trace["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] != "M"]
    # timestamps monotonic non-decreasing after the metadata prologue
    ts = [e["ts"] for e in spans]
    assert ts == sorted(ts)
    # host phases nest inside their chunk span; device phases sit on the
    # sibling <group>/dev track and stay inside [tg1, tg5]
    names = {e["name"] for e in meta}
    assert "thread_name" in names
    for seq in range(3):
        chunk = next(e for e in spans if e["name"] == f"chunk:{seq}")
        sched = [e for e in spans
                 if e["name"] == "schedule"
                 and e["args"]["seq"] == seq][0]
        assert sched["tid"] == chunk["tid"]
        assert sched["ts"] >= chunk["ts"] - 1e-6
        assert sched["ts"] + sched["dur"] \
            <= chunk["ts"] + chunk["dur"] + 1e-6
        dev = [e for e in spans
               if e.get("cat") == "device" and e["args"]["seq"] == seq]
        assert [d["name"] for d in dev] == ["h2d", "launch", "kernel",
                                            "d2h"]
        assert all(d["tid"] != chunk["tid"] for d in dev)
        lo, hi = dev[0]["ts"], dev[-1]["ts"] + dev[-1]["dur"]
        assert lo >= chunk["ts"] - 1e-6
        assert hi <= chunk["ts"] + chunk["dur"] + 1e-6


def _scope_spans(tr):
    return [e for e in tr.chrome_events() if e.get("ph") == "X"]


def test_scope_writes_a_ring_span_with_ids_and_parent():
    tr = SpanTracer()
    with tr.scope("serve.drain", "serve"):
        with tr.scope("svc.pop", "service", jobs=4):
            time.sleep(0.001)
        with pytest.raises(ValueError):
            with tr.scope("svc.complete", "service", jobs=4):
                raise ValueError("boom")        # the span still closes
    spans = {e["name"]: e for e in _scope_spans(tr)}
    assert set(spans) == {"serve.drain", "svc.pop", "svc.complete"}
    assert spans["svc.pop"]["args"] == {"parent": "serve.drain", "jobs": 4}
    assert spans["svc.complete"]["args"]["parent"] == "serve.drain"
    assert spans["serve.drain"]["args"] == {"parent": None}
    assert spans["svc.pop"]["dur"] >= 1000.0            # us
    outer, inner = spans["serve.drain"], spans["svc.pop"]
    assert outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert tr.emitted == 3 and tr.dropped == 0
    # another thread's scopes have no parent from this one
    with tr.scope("outer", "a"):
        th = threading.Thread(target=lambda: tr.scope("other", "b")
                              .__enter__().__exit__(None, None, None))
        th.start()
        th.join(timeout=10)
    other = [e for e in _scope_spans(tr) if e["name"] == "other"]
    assert other[0]["args"]["parent"] is None


def test_labeled_scope_namespaces_its_track_once():
    tel = Telemetry()
    view = tel.labeled(runtime="r1")
    with view.tracer.scope("svc.wait", "service"):
        pass
    with view.tracer.scope("sched.take", "r1/accel", group="r1/accel"):
        pass
    names = {e["tid"]: e["args"]["name"] for e in tel.tracer.chrome_events()
             if e.get("name") == "thread_name"}
    tracks = {e["name"]: names[e["tid"]]
              for e in _scope_spans(tel.tracer)}
    assert tracks == {"svc.wait": "r1/service", "sched.take": "r1/accel"}


def test_scope_annotations_land_in_the_profiler_host_plane(tmp_path):
    """Under a live profiler session the scopes (and the bare
    annotations) appear on the trace's host plane, named ``repro.*``."""
    import glob
    import os

    import jax
    import jax.numpy as jnp
    from repro.telemetry.spans import annotation

    tr = SpanTracer()
    x = jnp.ones((8, 8))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.scope("exec.prefill", "accel", group="accel", rows=8):
            jax.block_until_ready(x @ x)
            with annotation("exec.decode", group="accel"):
                jax.block_until_ready(x + 1.0)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    found[ev.name] = plane.name
    assert set(found) == {"repro.exec.prefill", "repro.exec.decode"}
    assert all(p.startswith("/host:") for p in found.values())
    # the ring holds the scope alone
    assert [e["name"] for e in _scope_spans(tr)] == ["exec.prefill"]


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_exporter_writes_jsonl_prom_and_trace(tmp_path):
    tel = Telemetry()
    tel.registry.counter("reqs").add(5)
    tel.tracer.chunk(_record(), epoch=0)
    metrics = str(tmp_path / "metrics.jsonl")
    prom = str(tmp_path / "prom.txt")
    trace = str(tmp_path / "trace.json")
    exp = MetricsExporter(tel, metrics_path=metrics, interval_s=0.02,
                          trace_path=trace, prometheus_path=prom)
    with exp:
        # condition-based liveness wait (no fixed sleep): hold the
        # exporter open until it has written at least two periodic
        # snapshots, bounded so a dead exporter fails fast
        deadline = time.monotonic() + 5.0
        while exp.snapshots_written < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
    snaps = read_jsonl(metrics)
    assert len(snaps) >= 2                       # periodic + final
    assert snaps[-1]["final"] is True
    assert snaps[-1]["counters"]["reqs"] == 5
    assert "reqs 5" in open(prom).read()
    loaded = json.load(open(trace))
    assert any(e.get("cat") == "chunk" for e in loaded["traceEvents"])
    assert exp.trace_events_written == len(loaded["traceEvents"])


def test_exporter_final_only_mode(tmp_path):
    tel = Telemetry()
    metrics = str(tmp_path / "m.jsonl")
    exp = MetricsExporter(tel, metrics_path=metrics, interval_s=0)
    exp.start()                                  # no thread in final-only
    assert exp._thread is None
    exp.stop()
    assert len(read_jsonl(metrics)) == 1


# ---------------------------------------------------------------------------
# always-on wiring
# ---------------------------------------------------------------------------

def test_resolve_semantics():
    assert resolve(OFF) is None
    assert resolve(False) is None
    t = Telemetry()
    assert resolve(t) is t
    assert resolve(None) is telemetry_mod.default()


def _two_group_sched(telemetry):
    groups = {
        "big": GroupSpec("big", DeviceKind.BIG, init_throughput=4000.0),
        "lil": GroupSpec("lil", DeviceKind.LITTLE, init_throughput=2000.0),
    }
    execs = {"big": SleepExecutor(rate=4000.0),
             "lil": SleepExecutor(rate=2000.0)}
    return DynamicScheduler(groups, execs, alpha=0.5, base_quantum=32,
                            telemetry=telemetry)


def test_scheduler_telemetry_snapshot_counts_chunks():
    tel = Telemetry()
    sched = _two_group_sched(tel)
    res = sched.run(0, 512)
    assert res.iterations == 512
    snap = sched.telemetry_snapshot()
    counters = snap["counters"]
    chunks = sum(v for k, v in counters.items()
                 if k.startswith("sched.chunks"))
    items = sum(v for k, v in counters.items()
                if k.startswith("sched.items"))
    assert chunks == len(res.records)
    assert items == 512
    # epochs_submitted carries a tier label since the latency-tier work
    assert sum(v for k, v in counters.items()
               if k.startswith("sched.epochs_submitted")) == 1
    assert counters["sched.epochs_finalized"] == 1
    assert "contention" in snap
    hists = snap["histograms"]
    per_group = [k for k in hists if k.startswith("sched.chunk_host_s")]
    assert per_group and all(hists[k]["count"] > 0 for k in per_group)
    # chunk spans reached the tracer with epoch + group tags
    chunk_events = [e for e in tel.tracer.chrome_events()
                    if e.get("cat") == "chunk"]
    assert len(chunk_events) == len(res.records)
    assert {e["args"]["group"] for e in chunk_events} == {"big", "lil"}
    sched.shutdown()


def test_scheduler_off_means_uninstrumented():
    sched = _two_group_sched(OFF)
    res = sched.run(0, 128)
    assert res.iterations == 128
    assert sched.telemetry_snapshot() is None
    sched.shutdown()


def test_serve_trace_golden_two_group_run():
    """2-group serve run through JobService: the exported Chrome trace is
    structurally valid (metadata prologue, monotonic timestamps, chunk
    spans tagged with tenant composition + epoch)."""
    tel = Telemetry()

    def make_scheduler():
        return _two_group_sched(tel)

    svc = JobService(make_scheduler, batch_jobs=4, telemetry=tel)
    jobs = [Job(items=64, tenant="gold" if i % 2 else "free")
            for i in range(8)]
    for j in jobs:
        svc.submit(j)
    assert svc.run_until_idle(timeout_s=30)
    # snapshot BEFORE close: the scheduler's banked completion batches
    # drain through a weak collector that dies with the scheduler
    snap = tel.snapshot()
    svc.close()
    trace = tel.tracer.chrome_trace()
    evs = trace["traceEvents"]
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": 0,
                      "args": {"name": "repro serving runtime"}}
    spans = [e for e in evs if e["ph"] != "M"]
    ts = [e["ts"] for e in spans]
    assert ts == sorted(ts)
    chunk_events = [e for e in spans if e.get("cat") == "chunk"]
    assert chunk_events
    for e in chunk_events:
        assert e["args"]["group"] in ("big", "lil")
        assert e["args"]["epoch"] >= 0
        assert set(e["args"]["tenants"]) <= {"gold", "free"}
    # service-layer metrics landed in the same registry
    counters = snap["counters"]
    assert counters["svc.batches"] >= 1
    done = sum(v for k, v in counters.items()
               if k.startswith('svc.jobs{state="done"'))
    assert done == 8
    assert any(k.startswith("queue.queue_delay_s")
               for k in snap["histograms"])


# ---------------------------------------------------------------------------
# torn-snapshot fixes (satellite)
# ---------------------------------------------------------------------------

def test_throughput_stats_returns_copy():
    tr = ThroughputTracker(alpha=0.5)
    rec = _record()
    tr.update(rec)
    st = tr.stats("g0")
    st.total_items += 10_000          # mutate the returned snapshot
    st.n += 5
    fresh = tr.stats("g0")
    assert fresh.total_items == rec.token.chunk.size
    assert fresh.n == 1


def test_overhead_totals_returns_copy():
    led = OverheadLedger()
    led.add(_record())
    tot = led.totals("g0")
    tot.sp += 100.0
    tot.n_chunks += 7
    fresh = led.totals("g0")
    assert fresh.n_chunks == 1
    assert fresh.sp < 100.0


def test_partitioner_contention_stats_consistent_pair():
    sched = _two_group_sched(OFF)
    sched.run(0, 256)
    stats = sched.partitioner.contention_stats()
    assert set(stats) == {"lock_wait_s", "lock_acquires"}
    assert stats["lock_acquires"] >= 1.0
    sched.shutdown()
