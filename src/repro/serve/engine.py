"""HeteroServeEngine: the paper's scheduler applied to batched inference.

The iteration space is the request queue; a chunk is a batch of requests. The
accelerator group's tuned chunk G is the throughput-optimal serving batch
(found with the same §3.2 search — too small under-fills the MXU, too large
blows the KV-cache working set); other groups get λ-proportional batches.
Each chunk is prefill + a fixed decode burst; effective throughput is
generated tokens / wall time, which feeds eq. (4) exactly like training.
"""
from __future__ import annotations

import os
import tempfile
import threading
import time
import weakref
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as telemetry_mod
from repro.configs.base import LMConfig
from repro.core import (ChunkRecord, DeviceKind, DynamicScheduler, GroupSpec,
                        JaxChunkExecutor, OverheadLedger, ThroughputTracker)
from repro.core.energy import EnergyModel
from repro.models import model as M
from repro.queue import (AdmissionController, Job, JobService, JournalStore,
                         QueueManager, percentiles)
from repro.tenancy import (ShardedQueueManager, TenantAccountant,
                           TenantRegistry)
from repro.train.trainer import GroupDef, bucket


@dataclass
class GroupOutputs:
    """What one group's chunks produced in one serve call: the devices
    their output arrays lived on, the range of the generated token ids,
    and the first chunk's request rows with its generated tokens (the
    whole padded batch), so a caller can recompute that chunk outside
    the scheduler."""
    chunks: int = 0
    devices: set = field(default_factory=set)
    token_min: int = 0
    token_max: int = 0
    rows: List[int] = field(default_factory=list)
    tokens: Optional[np.ndarray] = None

    def add(self, rows: np.ndarray, tokens: np.ndarray, devices) -> None:
        if self.chunks == 0:
            self.rows = [int(r) for r in rows if r >= 0]
            self.tokens = tokens
            self.token_min, self.token_max = int(tokens.min()), \
                int(tokens.max())
        else:
            self.token_min = min(self.token_min, int(tokens.min()))
            self.token_max = max(self.token_max, int(tokens.max()))
        self.chunks += 1
        self.devices.update(f"{d.platform}:{d.id}" for d in devices)

    def report(self) -> Dict:
        return {"chunks": self.chunks, "devices": sorted(self.devices),
                "token_range": [self.token_min, self.token_max],
                "sample": {"rows": self.rows,
                           "tokens": self.tokens.tolist()}}


@dataclass
class ServeReport:
    requests: int
    new_tokens: int
    time_s: float
    per_group_items: Dict[str, int]
    overheads: Dict[str, Dict[str, float]]
    throughput: Dict[str, float]
    dead_groups: List[str] = field(default_factory=list)


@dataclass
class QueueServeReport:
    """Result of the queued-submission path (serve_jobs)."""
    jobs: int
    done: int
    failed: int
    cancelled: int
    requeues: int
    batches: int
    new_tokens: int
    time_s: float
    queue_delay: Dict[str, float]          # p50/p95/p99 seconds
    per_group_items: Dict[str, int]
    throughput: Dict[str, float]
    dead_groups: List[str] = field(default_factory=list)
    drained: bool = True
    # multi-tenant mode: per-tenant attributed usage (items, busy_s,
    # energy_j, edp, queue-delay percentiles) + admission counters
    per_tenant: Dict[str, Dict] = field(default_factory=dict)
    admission_per_tenant: Dict[str, Dict[str, int]] = \
        field(default_factory=dict)
    # latency tiers: per-tier deadline misses, express-lane batches, and
    # in-flight epochs cancelled (deadline preemption)
    deadline_misses: Dict[str, int] = field(default_factory=dict)
    express_batches: int = 0
    cancelled_batches: int = 0
    # per-group GroupOutputs.report(): output devices, token range, and
    # one sampled chunk
    outputs: Dict[str, Dict] = field(default_factory=dict)


@dataclass
class FederatedServeReport:
    """Result of the federated path (serve_jobs_federated): the
    federation-level report plus engine-level aggregates."""
    fed: "object"                          # repro.federation.FederationReport
    drained: bool
    per_tenant: Dict[str, Dict] = field(default_factory=dict)
    new_tokens: int = 0
    outputs: Dict[str, Dict] = field(default_factory=dict)


class HeteroServeEngine:
    def __init__(self, cfg: LMConfig, groups: List[GroupDef],
                 prompt_len: int = 32, decode_tokens: int = 8,
                 max_len: Optional[int] = None, seed: int = 0,
                 alpha: float = 0.5, chunk_mode: str = "range",
                 telemetry=None, adaptive_refill: bool = True):
        self.cfg = cfg
        self.groups = groups
        self.prompt_len = prompt_len
        self.decode_tokens = decode_tokens
        self.max_len = max_len or bucket(prompt_len + decode_tokens)
        self.seed = seed
        self.alpha = alpha
        # history-driven refill sizing in the partitioner (steal-rate
        # feedback; see HeterogeneousPartitioner._refill_quota_locked)
        self.adaptive_refill = adaptive_refill
        # "range": zero-contention dispatch (private λ-share ranges with
        # work stealing); "paper": the lock-per-token baseline
        self.chunk_mode = chunk_mode
        # one Telemetry instance threaded through every layer the engine
        # builds (scheduler, partitioner, queue, admission, service) so
        # metrics and spans land in a single registry/tracer
        self.telemetry = telemetry_mod.resolve(telemetry)
        self.params = M.init_params(cfg, jax.random.PRNGKey(seed))
        # one copy of the params per bound device (federated runtimes on
        # a multi-device host); the default device uses self.params
        self._device_params: Dict[object, object] = {}
        self._device_params_lock = threading.Lock()
        self._fns: Dict[tuple, tuple] = {}
        # per executor key: what its chunks produced in the current serve
        # call (reset at the start of each; one writer per key)
        self._outputs: Dict[str, GroupOutputs] = {}
        # fail-injection counters persist across executors so an injected
        # group death stays dead over a queued multi-batch run
        self._fail_counters: Dict[str, Dict[str, int]] = {}
        # executors are built once per group and reused across epochs /
        # scheduler rebuilds (their jitted fns and inflight pipelines are
        # runtime-scoped, not batch-scoped)
        self._executors: Dict[str, JaxChunkExecutor] = {}

    # ------------------------------------------------------------------
    def _fns_for(self, b: int):
        # keyed by the step count too, so that a program never decodes
        # another count than the step that calls it announces
        steps = self.decode_tokens - 1
        if (b, steps) in self._fns:
            return self._fns[b, steps]
        cfg = self.cfg

        @jax.jit
        def prefill_fn(params, tokens, prefix):
            return M.prefill(cfg, params, tokens, prefix,
                             max_len=self.max_len)

        # a chunk's whole greedy decode, from the prefill's first token:
        # one program, the cache donated and written in place at each step
        @partial(jax.jit, donate_argnums=(1,))
        def decode_fn(params, cache, tokens):
            return M.decode_loop(cfg, params, cache, tokens, steps)

        self._fns[b, steps] = (prefill_fn, decode_fn)
        return self._fns[b, steps]

    def _prompt(self, idx: int, rng_salt: int = 0) -> np.ndarray:
        rng = np.random.Generator(np.random.PCG64(
            (self.seed << 32) ^ (idx + rng_salt)))
        return rng.integers(0, self.cfg.vocab, self.prompt_len,
                            dtype=np.int32)

    def _params_on(self, device):
        if device is None or \
                jax.tree.leaves(self.params)[0].devices() == {device}:
            return self.params
        with self._device_params_lock:
            p = self._device_params.get(device)
            if p is None:
                p = self._device_params[device] = \
                    jax.device_put(self.params, device)
            return p

    def _make_executor(self, g: GroupDef, key: Optional[str] = None,
                       device=None):
        cfg = self.cfg
        key = key or g.name
        device = g.device if g.device is not None else device
        params = self._params_on(device)

        def make_inputs(token):
            c = token.chunk
            pad = bucket(c.size)
            toks = np.stack([self._prompt(i) for i in range(c.begin, c.end)])
            if pad > c.size:
                toks = np.concatenate(
                    [toks, np.zeros((pad - c.size, self.prompt_len),
                                    np.int32)])
            # request row of each batch row (-1: padding), carried through
            # the step so a chunk's output says which requests it answers
            rows = np.full(pad, -1, np.int32)
            rows[:c.size] = np.arange(c.begin, c.end, dtype=np.int32)
            out = {"tokens": toks, "rows": rows}
            if cfg.prefix_len:
                rngp = np.random.Generator(np.random.PCG64(c.begin))
                out["prefix_emb"] = rngp.standard_normal(
                    (pad, cfg.prefix_len, cfg.d_model)).astype(np.float32) \
                    * 0.02
            return out

        counter = self._fail_counters.setdefault(key, {"n": 0})
        tel = self.telemetry
        if tel is not None:
            # rows served and bucket padding, counted as chunks complete
            real_rows = tel.registry.counter("exec.rows", group=key,
                                             kind="real")
            padded_rows = tel.registry.counter("exec.rows", group=key,
                                               kind="padded")
            # device bytes of the decode caches that this executor's
            # chunks hold, observed as each chunk's prefill and decode
            # calls are issued: a chunk holds its cache from its prefill
            # until its outputs are fetched (or dropped unfetched)
            cache_bytes = tel.registry.histogram("exec.cache_bytes_in_flight",
                                                 group=key)
            held = {"bytes": 0}
            held_lock = threading.Lock()

            def hold(outputs, nbytes: int) -> None:
                with held_lock:
                    held["bytes"] += nbytes
                    cache_bytes.observe(held["bytes"])
                weakref.finalize(outputs, release, nbytes)

            def release(nbytes: int) -> None:
                with held_lock:
                    held["bytes"] -= nbytes

        def step(batch):
            if g.fail_after_chunks is not None:
                counter["n"] += 1
                if counter["n"] > g.fail_after_chunks:
                    from repro.core.dispatch import ChunkFailure
                    raise ChunkFailure(f"group {g.name} injected failure")
            b = batch["tokens"].shape[0]
            prefill_fn, decode_fn = self._fns_for(b)
            if g.slowdown > 1.0:
                time.sleep((g.slowdown - 1.0) * 0.001 * b)
            with self._scope("exec.prefill", key, group=key, rows=b):
                logits, cache = prefill_fn(params, batch["tokens"],
                                           batch.get("prefix_emb"))
                tok = jnp.argmax(logits[:, -1], -1)[:, None] \
                    .astype(jnp.int32)
            # the cache's size from its shapes: no wait for the device
            nbytes = sum(a.nbytes for a in jax.tree.leaves(cache))
            gen, steps = tok, self.decode_tokens - 1
            if steps:
                with telemetry_mod.annotate(tel, "exec.decode", group=key,
                                            rows=b, tokens=steps):
                    rest, _ = decode_fn(params, cache, tok)
                    gen = jnp.concatenate([tok, rest], axis=1)
            if tel is not None:
                hold(gen, nbytes)
            return gen, batch["rows"]

        def fetch(outs):
            gen, rows = outs
            # a copy: on the CPU a view would keep the outputs, and so the
            # chunk's hold on its cache, alive for as long as the tokens
            tokens = np.array(gen)
            rows = np.asarray(rows)
            if tel is not None:
                real = int(np.count_nonzero(rows >= 0))
                real_rows.add(real)
                padded_rows.add(rows.shape[0] - real)
            self._outputs.setdefault(key, GroupOutputs()).add(
                rows, tokens, gen.devices())
            return {"tokens_out": tokens}

        return JaxChunkExecutor(step, make_inputs, fetch, device=device,
                                async_depth=g.async_depth,
                                priority_boost=g.priority_boost,
                                telemetry=self._tel_arg())

    def _executor_for(self, g: GroupDef, namespace: str = "",
                      device=None) -> JaxChunkExecutor:
        # executors (and fail-injection counters) are cached per
        # *namespaced* name: federated runtimes must not share one
        # executor's async pipeline across their dispatcher threads
        key = namespace + g.name
        ex = self._executors.get(key)
        if ex is None:
            ex = self._executors[key] = self._make_executor(g, key, device)
        return ex

    # ------------------------------------------------------------------
    def _build_scheduler(self, max_chunk: Optional[int] = None,
                         exclude: Optional[set] = None,
                         namespace: str = "",
                         telemetry=None,
                         wrap_executor: Optional[Callable] = None,
                         device=None) -> DynamicScheduler:
        """``namespace`` prefixes every group name (federation: runtime
        ``r1``'s accel group is ``r1/accel``), so per-runtime schedulers
        get private executors, distinct trace tracks, and unambiguous
        dead-group exclusion. ``wrap_executor(name, ex)`` decorates each
        group's executor (the chaos plane's injection point). ``device``
        binds groups that name no device of their own (and a copy of the
        params) to that device."""
        specs, execs = {}, {}
        for g in self.groups:
            name = namespace + g.name
            if exclude and name in exclude:
                continue
            specs[name] = GroupSpec(name, g.kind,
                                    fixed_chunk=g.fixed_chunk,
                                    min_chunk=1, max_chunk=max_chunk,
                                    init_throughput=1.0)
            ex = self._executor_for(g, namespace, device)
            if wrap_executor is not None:
                ex = wrap_executor(name, ex)
            execs[name] = ex
        if not specs:
            raise RuntimeError("no live device groups")
        return DynamicScheduler(specs, execs, alpha=self.alpha,
                                chunk_mode=self.chunk_mode,
                                adaptive_refill=self.adaptive_refill,
                                telemetry=telemetry if telemetry is not None
                                else self._tel_arg())

    def _scope(self, name: str, tid: str, **ids):
        return telemetry_mod.scope(self.telemetry, name, tid, **ids)

    def _tel_arg(self):
        """Forward the engine's resolved telemetry to a component ctor
        (None after resolve means *uninstrumented*, so pass OFF, not
        None — None would re-resolve to the process default)."""
        return self.telemetry if self.telemetry is not None \
            else telemetry_mod.OFF

    def telemetry_snapshot(self) -> Optional[Dict]:
        """Merged metrics + trace snapshot, or None when uninstrumented."""
        if self.telemetry is None:
            return None
        return self.telemetry.snapshot()

    def _outputs_report(self) -> Dict[str, Dict]:
        return {k: o.report() for k, o in sorted(self._outputs.items())}

    def serve(self, n_requests: int) -> ServeReport:
        self._outputs = {}
        sched = self._build_scheduler(max_chunk=n_requests)
        res = sched.run(0, n_requests)
        return ServeReport(
            requests=res.iterations,
            new_tokens=res.iterations * self.decode_tokens,
            time_s=res.total_time,
            per_group_items=res.per_group_items,
            overheads=res.overheads,
            throughput=res.throughput,
            dead_groups=sorted(res.failed_groups))

    # ------------------------------------------------------------------
    # queued-submission path: requests arrive as prioritized Jobs, pass
    # admission control, and are drained batch-wise by a JobService.
    # ------------------------------------------------------------------
    def serve_jobs(self, jobs: List[Job],
                   slo_delay_s: Optional[float] = None,
                   batch_jobs: int = 8,
                   journal_path: Optional[str] = None,
                   timeout_s: float = 300.0,
                   pipeline_depth: int = 2,
                   persistent: bool = True,
                   tenants: Optional[TenantRegistry] = None,
                   energy_model: Optional[EnergyModel] = None,
                   express: bool = True,
                   policy=None, idle_s: float = 0.0) \
            -> QueueServeReport:
        """Serve prioritized jobs through admission control + queue.

        Batches drain onto one *persistent* scheduler runtime: dispatcher
        threads and (cached) executors are built once and reused across
        epochs, and with ``pipeline_depth ≥ 2`` batch N+1 is dispatched
        while batch N is still in flight (continuous double-buffered
        drain — no inter-batch barrier, no per-batch rebuild).
        λ-estimates and overhead fractions are runtime-scoped (one
        ThroughputTracker / OverheadLedger for the whole session), so
        admission's capacity model and the partitioner both warm up once
        and stay warm. ``slo_delay_s=None`` disables the admission gate
        (every job is queued). Groups that die mid-run stay excluded for
        the rest of the session. ``persistent=False`` restores the old
        rebuild-per-batch behavior (benchmark baseline).

        Multi-tenant mode: pass a ``tenants`` registry and jobs are
        sharded per ``job.tenant`` with a DWRR weighted-fair drain,
        quota-aware admission (when an SLO enables the gate), and
        per-tenant accounting; with an ``energy_model`` each tenant's
        attributed joules/EDP are reported and soft energy budgets derate
        DWRR weights. Without a registry nothing changes.

        Latency tiers: urgent jobs drain through the service's express
        lane (``express=False`` disables it, the benchmark baseline),
        batches run at the tier of their most urgent member, and jobs
        with ``deadline_s`` are shed at pop or cooperatively cancelled in
        flight once the budget is spent.

        ``policy`` (repro.policy.AdaptivePolicy) smooths admission over a
        sliding window and cools down straggler rebalances. ``idle_s > 0``
        keeps the drain daemon parked for that long after the queue
        drains — the idle-efficiency probe scripts/smoke.sh uses to
        assert the event-driven drain isn't busy-polling.
        """
        self._outputs = {}
        with self._scope("serve.build", "serve"):
            tracker = ThroughputTracker(self.alpha)
            ledger = OverheadLedger()
            ledger.keep_records = False       # bounded memory for long runs
            dead: set = set()

            def make_scheduler() -> DynamicScheduler:
                # called once for the persistent runtime; again only if
                # every group died (or per batch with persistent=False)
                sched = self._build_scheduler(exclude=dead)
                sched.tracker = tracker           # runtime-scoped λ / §3.3
                sched.ledger = ledger
                return sched

            accountant = None
            if tenants is not None:
                queue = ShardedQueueManager(tenants, telemetry=self._tel_arg())
                accountant = TenantAccountant(tenants,
                                              energy_model=energy_model)
            else:
                queue = QueueManager()
            admission = None
            # the gate also turns on when any tenant spec carries an SLO or
            # quota — otherwise those contracts would be silently inert
            # without a global --slo; with no global SLO the global delay
            # band is infinite and only the per-tenant contracts bind
            if slo_delay_s is not None or (tenants is not None
                                           and tenants.any_gating()):
                admission = AdmissionController(
                    queue, tracker, ledger,
                    slo_delay_s=slo_delay_s if slo_delay_s is not None
                    else float("inf"),
                    registry=tenants, telemetry=self._tel_arg(),
                    policy=policy)
                for g in self.groups:
                    admission.on_group_join(g.name, 1.0)
            journal = JournalStore(journal_path) if journal_path else None
            service = JobService(make_scheduler, queue=queue,
                                 admission=admission, journal=journal,
                                 batch_jobs=batch_jobs,
                                 on_group_failed=dead.add,
                                 pipeline_depth=pipeline_depth,
                                 persistent=persistent,
                                 accountant=accountant,
                                 telemetry=self._tel_arg(),
                                 express=express)
        t0 = time.monotonic()
        with self._scope("serve.submit", "serve", jobs=len(jobs)):
            for job in jobs:
                service.submit(job)
        with self._scope("serve.drain", "serve"):
            drained = service.run_until_idle(timeout_s=timeout_s)
        dt = time.monotonic() - t0
        if idle_s > 0.0:
            # park the daemon on an empty queue: with the event-driven
            # drain it should accrue only fallback-timeout wakeups, at
            # ≤ 1/fallback_s per second (vs. 1/poll_s busy-polling)
            service.start()
            time.sleep(idle_s)
        with self._scope("serve.close", "serve"):
            service.close()
            if journal is not None:
                journal.close()
        st = service.stats
        cancelled = sum(1 for j in jobs if j.state.value == "cancelled")
        done_items = sum(j.items for j in jobs if j.state.value == "done")
        return QueueServeReport(
            jobs=len(jobs), done=st.done, failed=st.failed,
            cancelled=cancelled, requeues=st.requeues, batches=st.batches,
            new_tokens=done_items * self.decode_tokens, time_s=dt,
            queue_delay=percentiles(st.queue_delays),
            per_group_items=dict(st.per_group_items),
            throughput=tracker.snapshot(), dead_groups=sorted(dead),
            drained=drained,
            per_tenant=accountant.snapshot() if accountant else {},
            admission_per_tenant=dict(admission.per_tenant)
            if admission is not None else {},
            deadline_misses=dict(st.deadline_misses),
            express_batches=st.express_batches,
            cancelled_batches=st.cancelled_batches,
            outputs=self._outputs_report())

    # ------------------------------------------------------------------
    # federated path: N runtimes behind one front-end (repro.federation)
    # ------------------------------------------------------------------
    def serve_jobs_federated(self, jobs: List[Job],
                             runtimes: int = 3,
                             slo_delay_s: Optional[float] = None,
                             batch_jobs: int = 8,
                             journal_dir: Optional[str] = None,
                             timeout_s: float = 300.0,
                             pipeline_depth: int = 2,
                             tenants: Optional[TenantRegistry] = None,
                             energy_model: Optional[EnergyModel] = None,
                             express: bool = True,
                             heartbeat_s: float = 0.1,
                             kill_runtime: Optional[int] = None,
                             kill_after_frac: float = 0.5,
                             chaos_seed: Optional[int] = None,
                             chaos_plan: Optional[str] = None,
                             chaos_horizon_s: float = 2.0) \
            -> "FederatedServeReport":
        """Serve jobs through a ``FederatedService``: ``runtimes``
        independent JobService runtimes — each with its own persistent
        scheduler (namespaced device groups ``rK/<group>``, private
        executors), runtime-scoped λ-tracker/ledger, tenancy shards, and
        mirrored journal — behind one tenant-consistent-hash front door.
        Global tenant quotas / energy budgets bind fleet-wide via gossip.

        ``kill_runtime=K`` crashes runtime ``rK`` once ``kill_after_frac``
        of the jobs are done (failure drill: its replica replays onto a
        survivor; the report's ``recovered`` counts the requeued jobs).

        Chaos plane: ``chaos_seed`` generates a deterministic randomized
        ``FaultPlan`` over ``chaos_horizon_s`` seconds (same seed ⇒ same
        schedule); ``chaos_plan`` instead loads an explicit plan (a JSON
        string or a path to one). Executor faults wrap every group's
        executor; journal/federation faults are executed by the
        federation tier.

        On a host with several devices, runtime ``rK``'s executors and a
        copy of the params are bound to ``jax.devices()[K % n]`` (one
        replica per chip); with one device everything stays on it.
        """
        from repro.chaos import ChaosExecutor, ChaosInjector, FaultPlan
        from repro.federation import FederatedService
        t_serve = time.monotonic()
        if journal_dir is None:
            journal_dir = tempfile.mkdtemp(prefix="repro-fed-")
        self._outputs = {}
        rids = [f"r{i}" for i in range(max(1, runtimes))]
        devices = jax.devices()
        bound = {rid: devices[i % len(devices)] if len(devices) > 1 else None
                 for i, rid in enumerate(rids)}

        chaos = None
        if chaos_plan is not None or chaos_seed is not None:
            if chaos_plan is not None:
                text = chaos_plan
                if os.path.exists(chaos_plan):
                    with open(chaos_plan, "r", encoding="utf-8") as fh:
                        text = fh.read()
                plan = FaultPlan.from_json(text)
            else:
                plan = FaultPlan.generate(
                    chaos_seed, chaos_horizon_s, rids,
                    [f"{rid}/{g.name}" for rid in rids
                     for g in self.groups])
            chaos = ChaosInjector(plan, telemetry=self._tel_arg())

        def make_service(rid: str, journal, telemetry) -> JobService:
            tracker = ThroughputTracker(self.alpha)
            ledger = OverheadLedger()
            ledger.keep_records = False
            dead: set = set()

            def make_scheduler() -> DynamicScheduler:
                wrap = None
                if chaos is not None:
                    def wrap(name, ex):
                        return ChaosExecutor(ex, name, chaos)
                sched = self._build_scheduler(exclude=dead,
                                              namespace=f"{rid}/",
                                              telemetry=telemetry,
                                              wrap_executor=wrap,
                                              device=bound[rid])
                sched.tracker = tracker
                sched.ledger = ledger
                return sched

            accountant = None
            if tenants is not None:
                queue = ShardedQueueManager(tenants, telemetry=telemetry)
                accountant = TenantAccountant(tenants,
                                              energy_model=energy_model)
            else:
                queue = QueueManager()
            admission = None
            if slo_delay_s is not None or (tenants is not None
                                           and tenants.any_gating()):
                admission = AdmissionController(
                    queue, tracker, ledger,
                    slo_delay_s=slo_delay_s if slo_delay_s is not None
                    else float("inf"),
                    registry=tenants, telemetry=telemetry)
                for g in self.groups:
                    admission.on_group_join(f"{rid}/{g.name}", 1.0)
            return JobService(make_scheduler, queue=queue,
                              admission=admission, journal=journal,
                              batch_jobs=batch_jobs,
                              on_group_failed=dead.add,
                              pipeline_depth=pipeline_depth,
                              accountant=accountant,
                              telemetry=telemetry, express=express)

        with self._scope("serve.build", "serve", runtimes=len(rids)):
            fed = FederatedService(make_service, rids, journal_dir,
                                   tenants=tenants,
                                   telemetry=self._tel_arg(),
                                   heartbeat_s=heartbeat_s,
                                   chaos=chaos)
            t0 = time.monotonic()
            fed.start()
        with self._scope("serve.submit", "serve", jobs=len(jobs)):
            for job in jobs:
                fed.submit(job)
        victim = f"r{kill_runtime}" if kill_runtime is not None \
            and 0 <= kill_runtime < len(rids) else None
        with self._scope("serve.drain", "serve"):
            if victim is not None:
                threshold = max(1, int(kill_after_frac * len(jobs)))
                deadline = time.monotonic() + timeout_s
                while time.monotonic() < deadline:
                    done = sum(1 for j in jobs
                               if j.state.value in ("done", "failed",
                                                    "cancelled"))
                    if done >= threshold:
                        break
                    time.sleep(0.01)
                fed.kill_runtime(victim)
            drained = fed.run_until_idle(timeout_s=timeout_s)
        rep = fed.report()
        rep.time_s = time.monotonic() - t0
        per_tenant: Dict[str, Dict] = {}
        for node in fed.nodes().values():
            acct = node.service.accountant
            if acct is None:
                continue
            for t, d in acct.snapshot().items():
                agg = per_tenant.setdefault(
                    t, {"items": 0, "busy_s": 0.0, "energy_j": 0.0,
                        "batches": 0})
                agg["items"] += d["items"]
                agg["busy_s"] += d["busy_s"]
                agg["energy_j"] += d["energy_j"]
                agg["batches"] += d["batches"]
        with self._scope("serve.close", "serve"):
            fed.close()
        if self.telemetry is not None:
            # the whole call, against which each runtime's epoch spans
            # (``rK/epochs``) show how long it sat drained
            self.telemetry.tracer.span("serve", "serve", t_serve,
                                       time.monotonic(), runtimes=rids)
        return FederatedServeReport(
            fed=rep, drained=drained, per_tenant=per_tenant,
            new_tokens=sum(rep.per_tenant_items.values())
            * self.decode_tokens,
            outputs=self._outputs_report())
