"""One run of one cell: set-up, the measured window, metrics, the check.

The window drives the program's queued serve entry,
``HeteroServeEngine.serve_jobs`` (``serve_jobs_federated`` where the
traffic asks for several runtimes), built as the serve CLI builds it:
``parse_groups``, ``TenantRegistry.parse``, a ``Telemetry`` with the CLI's
snapshot exporter. One client sends a wave of jobs, waits for all of them,
then sends the next; no wave starts after ``seconds``, and the wave in
flight completes and counts.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple
from unittest import mock

import numpy as np

from chipbench import spec as spec_mod
from chipbench import traffic as traffic_mod
from chipbench import work as work_mod
from chipbench.spec import HERE, ROOT, SpecError, family_of
from chipbench.trace import SPAN_PREFIX

PEAKS = HERE / "peaks.json"
#: waves the profiler records in a ``--trace 1`` run: on a v5e chip one
#: wave of chat decoding is ~10^6 device-op events (~50 MB of trace) and
#: writing them out takes the host some tens of seconds, so the device
#: metrics come from the window's first wave
TRACED_WAVES = 1


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS, encoding="utf-8") as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def buckets(max_items: int) -> List[int]:
    """Batch buckets the scheduler can draw for epochs of up to
    ``max_items`` items: every power of two up to the first at or above
    it (the engine pads a chunk to ``bucket(size)``)."""
    out, b = [], 1
    while b < max_items:
        out.append(b)
        b *= 2
    return out + [b]


@dataclass
class JobRecord:
    created: float
    started: Optional[float]
    finished: Optional[float]
    done: bool
    items: int


@dataclass
class Run:
    """What a metric reader may read. Times are seconds; host stamps on
    ``time.monotonic`` (job stamps on ``time.time``)."""
    chips: int
    peaks: Optional[Dict[str, float]]
    jobs: List[JobRecord] = field(default_factory=list)
    waves: int = 0
    window_s: float = 0.0
    mono_window: Tuple[float, float] = (0.0, 0.0)
    generated_tokens: int = 0
    tokens_per_sequence: int = 0
    tel_start: Dict = field(default_factory=dict)
    tel_end: Dict = field(default_factory=dict)
    spans: List[Dict] = field(default_factory=list)   # chrome trace events
    trace_dropped: int = 0
    trace: Any = None                                  # trace.Summary
    work: Dict[str, work_mod.Work] = field(default_factory=dict)
    memory_peak_bytes: int = 0
    per_runtime_items: List[Dict[str, float]] = field(default_factory=list)
    wave_s: List[float] = field(default_factory=list)  # each serve call
    group_items: Counter = field(default_factory=Counter)


def load_reader(name: str):
    path = spec_mod.data_dir() / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader metrics/{name}.py for metric {name!r}")
    module_spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def percentile(xs: List[float], p: float) -> float:
    """Nearest rank."""
    s = sorted(xs)
    k = max(0, min(len(s) - 1, int(np.ceil(p / 100.0 * len(s))) - 1))
    return s[k]


def roofline(run: "Run", program: str) -> Optional[float]:
    """Least time of ``program``'s traced work over its device time, %."""
    if run.trace is None or run.peaks is None:
        return None
    device_s = run.trace.program_s.get(program, 0.0)
    work = run.work.get(program)
    if device_s <= 0 or work is None or work.flops <= 0:
        return None
    least = work.least_seconds(run.peaks["flops_bf16"],
                               run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / device_s


def _program():
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  (fails where the checkout has no program)


def _check_sizes(cfg, sizes) -> None:
    have = family_of(sizes).sizes_of(cfg)
    if have != sizes:
        diff = {f: (getattr(have, f), getattr(sizes, f))
                for f in type(sizes).__dataclass_fields__
                if getattr(have, f) != getattr(sizes, f)}
        raise SpecError(f"{cfg.arch_id}: the program's sizes differ from "
                        f"the configuration file's (program, file): {diff}")


def _device_peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _job_record(j) -> JobRecord:
    return JobRecord(j.created_at, j.first_started_at, j.finished_at,
                     j.state.value == "done", j.items)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_process: float, bench: Optional[Dict] = None,
             rehearse: bool = False, control: bool = False,
             log=None) -> Dict:
    """One run; returns the result line's object plus ``"limits"``.
    ``rehearse`` runs the program's tiny same-family config on any
    backend: a rehearsal of the control flow, never a measurement.
    ``control`` also reads the fp8 control on the same served sequences
    (``chipbench.control``; the benchmark's own runs never do)."""
    import jax

    from chipbench import clock as clock_mod
    from chipbench import trace as trace_mod
    from chipbench import weights as weights_mod

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench = bench or spec_mod.load_benchmark()
    cell = spec_mod.cell(bench, cell_name)
    conf = spec_mod.config(cell.config)
    tr = spec_mod.traffic(cell.traffic)
    limit = spec_mod.check_limit(cell.name)

    all_devices = jax.devices()
    dev0 = all_devices[0]
    if dev0.platform != "tpu" and not rehearse:
        raise NoChip(f"no TPU: JAX found {dev0.platform} "
                     f"({dev0.device_kind})")
    if len(all_devices) < cell.chips and not rehearse:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(all_devices)}")
    devices = all_devices[:cell.chips]
    pk = peaks(dev0.device_kind) if dev0.platform == "tpu" else None

    _program()
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import parse_groups
    from repro.models import model as M
    from repro.serve.engine import HeteroServeEngine
    from repro.telemetry import MetricsExporter, Telemetry
    from repro.tenancy import TenantRegistry

    cache_dir = None
    if not rehearse:
        cache_dir = enable_compile_cache()
        # every program, however quick to compile, comes from the cache
        # on later runs, so set-up does the same work each time
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = clock_mod.CompileClock(jax)

    family = family_of(conf.sizes)
    cfg = family.program_config(get_config(conf.arch), conf.reduced)
    _check_sizes(cfg, conf.sizes)
    sizes = conf.sizes
    if rehearse:
        from repro.configs.base import reduced
        cfg = reduced(cfg)
        sizes = family.sizes_of(cfg)
    weights_mod.check_layout(sizes, M.abstract_params(cfg))
    params = weights_mod.make_params(sizes, seed, dev0)

    groups = parse_groups(tr.groups)
    registry = TenantRegistry.parse(tr.tenants)
    tel = Telemetry()
    exporter = MetricsExporter(tel, interval_s=1.0)
    # the engine's constructor draws its own weights; it gets the
    # benchmark's instead, so the reference can draw them again
    with mock.patch.object(M, "init_params", lambda _cfg, _key: params):
        eng = HeteroServeEngine(cfg, groups, prompt_len=tr.prompt_len,
                                decode_tokens=tr.decode_tokens, seed=seed,
                                telemetry=tel)
    del params

    _warm_up(jax, eng, groups[0], tr, all_devices)
    waves = traffic_mod.Waves(tr, seed, registry.names())
    run = Run(cell.chips, pk)
    exporter.start()
    run.tel_start = tel.snapshot()
    profiler = trace_mod.Profiler(enabled=trace)
    t_setup = time.perf_counter()
    setup_s = t_setup - t_process
    log(f"set-up {setup_s:.3f} s (compile events "
        f"{clock.seconds(t_process, t_setup):.3f} s, cache {cache_dir})")

    t0, t1, mono0, mono1, jobs_all, samples = _serve_waves(
        eng, tr, registry, waves, seconds, profiler, run)

    in_window = clock.compiles(t0, t1)
    exporter.stop()
    run.tel_end = tel.snapshot()
    run.spans = tel.tracer.chrome_events()
    run.trace_dropped = tel.tracer.dropped
    run.window_s = t1 - t0
    run.mono_window = (mono0, mono1)
    run.jobs = [_job_record(j) for j in jobs_all]
    done = [j for j in run.jobs if j.done]
    # tokens per sequence as the serve call returned them: every wave's
    # sampled chunks, each row a whole sequence
    widths = [toks.shape[1] for _, toks in samples]
    run.tokens_per_sequence = min(widths) if widths else 0
    wrong_length = sum(len(rows) for rows, toks in samples
                       if toks.shape[1] != tr.decode_tokens)
    run.generated_tokens = sum(j.items for j in done) \
        * run.tokens_per_sequence
    run.memory_peak_bytes = _device_peak_bytes(devices)
    log(f"window {run.window_s:.3f} s, {run.waves} waves, "
        f"{len(run.jobs)} jobs, {run.generated_tokens} tokens; programs "
        f"compiled or loaded in the window: {in_window}")
    log("wave seconds: " + " ".join(f"{s:.3f}" for s in run.wave_s)
        + f"; items per group: {dict(run.group_items)}")

    if trace:
        run.trace = profiler.summary()
        if run.trace is not None:
            run.work = _traced_work(run, sizes, tr.prompt_len)
        elif not rehearse:
            raise RuntimeError("the profiler trace holds no device "
                               "operation inside the window")

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for name in spec_mod.metric_names(bench, kind, cell.name):
        if name == "setup_s":
            value = setup_s
        else:
            value = load_reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value,
                             "unit": spec_mod.metric_unit(bench, name)}

    # the check: reference on a sample of what the window served, after
    # the program's state is freed (the telemetry's collectors hold the
    # schedulers, and through their executors the weights)
    del eng, tel, exporter
    gc.collect()
    t_ref = time.perf_counter()
    widest, in_vocab, control_gap, distinct = _check(samples, seed, sizes,
                                                     tr, control)
    log(f"reference: {min(tr.check_sequences, distinct)} of {distinct} "
        f"distinct served sequences, {time.perf_counter() - t_ref:.3f} s")
    failed = len(run.jobs) - len(done)
    limits = {
        "widest_logit_gap": [widest, limit],
        "jobs_not_done": [failed, 0],
        "compiles_in_window": [in_window, 0],
        "tokens_out_of_vocab": [int(not in_vocab), 0],
        "sequences_of_wrong_length": [wrong_length, 0],
    }
    correct = all(value <= bound for value, bound in limits.values())
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    out = {"correct": bool(correct), "attempted": len(run.jobs),
           "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    if control:
        # the control in the program's place: its picks judged at the
        # cell's own limits, every other number as the program read it
        held = dict(limits, widest_logit_gap=[control_gap, limit])
        out["control"] = {
            "program_gap": widest, "control_gap": control_gap,
            "control_correct": all(v <= b for v, b in held.values())}
    out["limits"] = limits
    return out


def _warm_up(jax, eng, group, tr, all_devices) -> None:
    """Each batch bucket's whole step (prefill, the decode loop, the
    argmax and concatenation around them) on every chip in use, through
    the executor that the serve path will reuse."""
    if tr.runtimes == 1:
        bound = [("", None)]
    else:          # serve_jobs_federated binds runtime rK to device K % n
        n = len(all_devices)
        bound = [(f"r{k}/", all_devices[k % n] if n > 1 else None)
                 for k in range(tr.runtimes)]
    seen = set()
    for ns, dev in bound:
        if dev in seen:
            continue
        seen.add(dev)
        ex = eng._executor_for(group, ns, dev)
        for b in buckets(tr.batch_jobs * tr.items_per_job):
            batch = {"tokens": np.zeros((b, tr.prompt_len), np.int32),
                     "rows": np.full(b, -1, np.int32)}
            put = jax.device_put(batch, dev) if dev is not None \
                else jax.device_put(batch)
            jax.block_until_ready(ex.step(put))


def _traced_work(run: Run, sizes, prompt_len: int) \
        -> Dict[str, work_mod.Work]:
    """Work of the chunks that ran inside the profiled window, by
    program: ``prefill`` and ``decode`` calls at each chunk's real item
    count and the tokens per sequence the serve call returned, as the
    family counts them."""
    lo, hi = run.trace.host_window
    chunk = family_of(sizes).chunk
    totals = {"prefill": work_mod.ZERO, "decode": work_mod.ZERO}
    for ev in run.spans:
        if ev.get("cat") != "chunk":
            continue
        start = ev["ts"] * 1e-6
        end = start + ev["dur"] * 1e-6
        if start < lo or end > hi:
            continue
        pre, dec = chunk(sizes, ev["args"]["items"], prompt_len,
                         run.tokens_per_sequence)
        totals["prefill"] += pre
        totals["decode"] += dec
    return totals


def _serve_waves(eng, tr, registry, waves, seconds, profiler, run):
    """The measured window: waves back to back until ``seconds`` have
    passed; the profiler, when on, records the first ``TRACED_WAVES``."""
    import jax.profiler as jprof
    from repro.queue import Job

    samples = []                     # (rows, tokens) per group per wave
    jobs_all = []
    mono0 = time.monotonic()
    profiler.start()
    t0 = time.perf_counter()
    while True:
        with jprof.TraceAnnotation(SPAN_PREFIX + "wave_submit"):
            jobs = [Job(items=tr.items_per_job, priority=i % 3,
                        tier="standard", tenant=t)
                    for i, t in enumerate(waves.tenants(run.waves))]
        with jprof.TraceAnnotation(SPAN_PREFIX + "wave_serve"):
            t_wave = time.perf_counter()
            if tr.runtimes == 1:
                rep = eng.serve_jobs(jobs, batch_jobs=tr.batch_jobs,
                                     pipeline_depth=tr.pipeline_depth,
                                     tenants=registry)
            else:
                rep = eng.serve_jobs_federated(
                    jobs, runtimes=tr.runtimes, batch_jobs=tr.batch_jobs,
                    pipeline_depth=tr.pipeline_depth, tenants=registry)
            run.wave_s.append(time.perf_counter() - t_wave)
            for g, n in getattr(rep, "per_group_items", {}).items():
                run.group_items[g] += n
        with jprof.TraceAnnotation(SPAN_PREFIX + "wave_collect"):
            jobs_all.extend(jobs)
            for out in rep.outputs.values():
                samples.append((out["sample"]["rows"],
                                np.asarray(out["sample"]["tokens"])))
            if tr.runtimes > 1:
                run.per_runtime_items.append(
                    {r: d["items"] for r, d in rep.fed.per_runtime.items()})
        run.waves += 1
        if run.waves == TRACED_WAVES:
            profiler.stop()
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    profiler.stop()
    return t0, t1, mono0, time.monotonic(), jobs_all, samples


def _check(samples, seed, sizes, tr, control: bool):
    """The reference over a sample, drawn from the seed, of the distinct
    served sequences: the widest logit gap of a served token, whether
    every token is in the vocabulary, (``control``) the fp8 control's
    widest gap on the same sequences, and how many distinct sequences
    there were to draw from."""
    from chipbench.reference import control_gaps, served_gaps

    rng = np.random.default_rng([seed, 7])
    # the engine makes request row i's prompt from (seed, i) in every
    # batch, so waves repeat rows: one entry per row and served tokens,
    # at the length most sequences came back with
    width = Counter(t.shape[1] for _, t in samples).most_common(1)[0][0]
    unique = {}
    for rows, toks in samples:
        if toks.shape[1] != width:
            continue
        for k, row in enumerate(rows):
            unique.setdefault((row, toks[k].tobytes()), (row, toks[k]))
    pool = [unique[key] for key in sorted(unique)]
    pick = rng.choice(len(pool), size=min(tr.check_sequences, len(pool)),
                      replace=False)
    prompts = np.stack([traffic_mod.prompt(seed, pool[i][0], sizes.vocab,
                                           tr.prompt_len) for i in pick])
    served = np.stack([pool[i][1] for i in pick]).astype(np.int32)
    in_vocab = bool(((served >= 0) & (served < sizes.vocab)).all())
    ref = family_of(sizes).Reference(sizes, seed)
    widest = float(served_gaps(ref, prompts, served).max())
    control_gap = float(control_gaps(ref, prompts, served).max()) \
        if control else None
    return widest, in_vocab, control_gap, len(pool)


def __getattr__(name):
    """The dense family's ``sizes_of``, under the name it had here."""
    if name == "sizes_of":
        from chipbench.families.dense import sizes_of
        return sizes_of
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
