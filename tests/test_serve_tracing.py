"""The serve path's own spans and counters: the executor's row and CPU
counters against what the serve call did, the scopes of each layer in
the span ring, the profiler annotations of a serve call on the host
plane, and the ``serve`` span of a federated call against its runtimes'
epoch spans."""
import glob
import os
import time

import pytest

from repro.configs.registry import get_reduced_config
from repro.core.types import DeviceKind
from repro.queue import Job
from repro.serve.engine import HeteroServeEngine
from repro.telemetry import Telemetry
from repro.train.trainer import GroupDef, bucket

DECODE_TOKENS = 3


@pytest.fixture(scope="module")
def engine():
    cfg = get_reduced_config("stablelm-1.6b").replace(
        n_layers=2, dtype="float32")
    # accel's chunks of 3 pad to the batch bucket of 4
    groups = [GroupDef("accel", DeviceKind.ACCEL, fixed_chunk=3,
                       async_depth=2),
              GroupDef("cpu0", DeviceKind.BIG)]
    return HeteroServeEngine(cfg, groups, prompt_len=8,
                             decode_tokens=DECODE_TOKENS,
                             telemetry=Telemetry())


def _diff(before, after, kind, prefix, field=None):
    out = {}
    for key, v in after[kind].items():
        if key.startswith(prefix):
            v0 = before[kind].get(key)
            if field is None:
                out[key] = v - (v0 or 0.0)
            else:
                out[key] = v[field] - (v0[field] if v0 else 0.0)
    return out


def _spans(tel, lo_us=0.0):
    evs = tel.tracer.chrome_events()
    names = {e["tid"]: e["args"]["name"] for e in evs
             if e.get("name") == "thread_name"}
    return [dict(e, track=names[e["tid"]]) for e in evs
            if e.get("ph") == "X" and e["ts"] >= lo_us]


def test_cache_in_flight_is_observed_per_chunk_and_released(engine):
    """Each chunk observes the decode cache its group holds: its own
    and, at most, that of the one chunk before it still in flight
    (accel's pipeline depth is 2). Once a serve call has fetched every
    chunk, the next call's chunks observe their own caches alone."""
    import jax

    from repro.models import model as M
    tel = engine.telemetry
    name = "exec.cache_bytes_in_flight"
    # accel's chunks of 3 and cpu0's of up to 4 pad to 4 rows
    one = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        M.init_cache(engine.cfg, 4, engine.max_len, abstract=True)))
    before = tel.snapshot()
    rep = engine.serve_jobs([Job(items=3) for _ in range(4)], batch_jobs=4,
                            timeout_s=120.0)
    after = tel.snapshot()
    assert rep.drained
    seen = _diff(before, after, "histograms", name, "count")
    assert set(seen) == {f'{name}{{group="accel"}}',
                         f'{name}{{group="cpu0"}}'}
    assert sum(seen.values()) >= 2
    assert after["histograms"][f'{name}{{group="accel"}}']["max"] <= 2 * one
    engine.serve_jobs([Job(items=2)], batch_jobs=4, timeout_s=120.0)
    last = tel.snapshot()
    count = _diff(after, last, "histograms", name, "count")
    total = _diff(after, last, "histograms", name, "sum")
    assert sum(count.values()) >= 1
    for key, n in count.items():
        assert total[key] <= n * one, key


def test_counters_match_what_the_serve_call_did(engine):
    tel = engine.telemetry
    before = tel.snapshot()
    t0 = time.monotonic() * 1e6
    jobs = [Job(items=3) for _ in range(10)]
    rep = engine.serve_jobs(jobs, batch_jobs=4, timeout_s=120.0)
    after = tel.snapshot()
    assert rep.drained and rep.done == 10
    chunks = [e for e in _spans(tel, t0) if e.get("cat") == "chunk"]
    sizes = [e["args"]["items"] for e in chunks]
    assert sum(sizes) == 30

    rows = _diff(before, after, "counters", "exec.rows")
    real = sum(v for k, v in rows.items() if 'kind="real"' in k)
    padded = sum(v for k, v in rows.items() if 'kind="padded"' in k)
    assert real == sum(j.items for j in jobs)
    assert padded == sum(bucket(n) - n for n in sizes) > 0
    # one issue and one wait reading per chunk, on each group
    for name in ("exec.issue_cpu_s", "exec.wait_cpu_s"):
        cpu_n = _diff(before, after, "histograms", name, "count")
        assert sum(cpu_n.values()) == len(chunks), name
        assert set(cpu_n) == {f'{name}{{group="accel"}}',
                              f'{name}{{group="cpu0"}}'}
    cpu_s = _diff(before, after, "histograms", "exec.issue_cpu_s", "sum")
    assert sum(cpu_s.values()) > 0.0
    # the device-time histogram named after a host-observed interval
    # is gone; the host overhead histogram stays
    assert not any(k.startswith("sched.chunk_device_s")
                   for k in after["histograms"])
    assert any(k.startswith("sched.chunk_host_s")
               for k in after["histograms"])


def test_each_layer_scopes_its_work_with_ids_and_parents(engine):
    tel = engine.telemetry
    t0 = time.monotonic() * 1e6
    engine.serve_jobs([Job(items=2) for _ in range(6)], batch_jobs=3,
                      timeout_s=120.0)
    spans = _spans(tel, t0)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    serve = [e["name"] for e in spans if e["track"] == "serve"]
    assert serve == ["serve.build", "serve.submit", "serve.drain",
                     "serve.close"]
    assert all(e["args"]["parent"] is None for e in spans
               if e["track"] == "serve")
    # the single-runtime drain runs on the caller's thread
    for name in ("svc.pop", "svc.submit", "svc.complete"):
        assert by_name[name]
        assert {e["args"]["parent"] for e in by_name[name]} \
            == {"serve.drain"}
        assert {e["track"] for e in by_name[name]} == {"service"}
    chunks = [e for e in spans if e.get("cat") == "chunk"]
    got = sorted((e["args"]["group"], e["args"]["seq"])
                 for e in by_name["exec.inputs"])
    assert got == sorted((e["args"]["group"], e["args"]["seq"])
                         for e in chunks)
    assert all(e["track"] == e["args"]["group"]
               for e in by_name["exec.inputs"])
    assert len(by_name["exec.prefill"]) == len(chunks)
    assert by_name["sched.await"] and by_name["sched.finalize"]
    # the decode calls, and the intervals a chunk's record already holds
    # as its schedule/h2d/kernel/d2h phases, are profiler annotations
    # alone, not ring entries
    for name in ("exec.decode", "sched.take", "exec.h2d", "exec.wait",
                 "exec.fetch"):
        assert name not in by_name, name
    assert tel.tracer.dropped == 0


def _profiled_events(logdir):
    """``repro.*`` event name -> [(plane name, the event's ids)]."""
    import jax
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.setdefault(ev.name, []).append(
                        (plane.name, dict(ev.stats)))
    return out


def test_profiler_sees_each_decode_call_and_chunk_phase(engine, tmp_path):
    """Under a profiler session each layer's work lands on the host plane
    as ``repro.*`` events: one ``exec.decode`` per chunk, the one call
    that decodes its ``decode_tokens - 1`` tokens, and one h2d/fetch per
    chunk."""
    import jax
    tel = engine.telemetry
    jobs = [Job(items=2) for _ in range(6)]
    engine.serve_jobs([Job(items=1)], timeout_s=120.0)  # compiled first
    before = tel.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = engine.serve_jobs(jobs, batch_jobs=3, timeout_s=120.0)
    finally:
        jax.profiler.stop_trace()
    after = tel.snapshot()
    assert rep.drained and rep.done == len(jobs)
    chunks = sum(_diff(before, after, "counters", "sched.chunks").values())
    assert chunks > 0
    found = _profiled_events(str(tmp_path))
    assert len(found["repro.exec.decode"]) == chunks
    assert all(ids["tokens"] == DECODE_TOKENS - 1
               for _, ids in found["repro.exec.decode"])
    for name in ("repro.exec.inputs", "repro.exec.h2d",
                 "repro.exec.prefill", "repro.exec.fetch"):
        assert len(found[name]) == chunks, name
    assert len(found["repro.sched.take"]) >= chunks
    for name in ("repro.serve.build", "repro.serve.drain", "repro.svc.pop",
                 "repro.svc.complete", "repro.sched.await"):
        assert found.get(name), name
    assert all(p.startswith("/host:") for evs in found.values()
               for p, _ in evs)


def test_federated_serve_span_brackets_its_runtimes_epochs(engine):
    tel = engine.telemetry
    t0 = time.monotonic() * 1e6
    rep = engine.serve_jobs_federated(
        [Job(items=1, tenant=f"t{i % 4}") for i in range(16)], runtimes=2,
        batch_jobs=4, timeout_s=120.0)
    assert rep.drained
    spans = _spans(tel, t0)
    serve = [e for e in spans if e["name"] == "serve"]
    assert len(serve) == 1 and serve[0]["track"] == "serve"
    assert serve[0]["args"]["runtimes"] == ["r0", "r1"]
    lo, hi = serve[0]["ts"], serve[0]["ts"] + serve[0]["dur"]
    epochs = [e for e in spans if e["name"].startswith("epoch:")]
    assert {e["track"] for e in epochs} <= {"r0/epochs", "r1/epochs"}
    assert epochs and all(lo <= e["ts"] and e["ts"] + e["dur"] <= hi
                          for e in epochs)
    inner = [e for e in spans if e["name"].startswith("serve.")]
    assert [e["name"] for e in inner] == ["serve.build", "serve.submit",
                                          "serve.drain", "serve.close"]
    # each runtime's drain and dispatchers keep to their own tracks:
    # a namespaced group keeps its one prefix
    tracks = {e["track"] for e in spans if e["name"].startswith("svc.")}
    assert tracks <= {"r0/service", "r1/service"}
    tracks = {e["track"] for e in spans if e["name"].startswith("sched.")}
    assert tracks <= {"r0/accel", "r0/cpu0", "r1/accel", "r1/cpu0"}
