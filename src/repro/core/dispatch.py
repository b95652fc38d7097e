"""Chunk executors: how a device group processes a chunk (Filter₂).

Executors fill the device-side timestamps of ChunkRecord:
  tg1→tg2  host-to-device transfer (jax.device_put of the chunk's inputs)
  tg2→tg3  dispatch / launch (the jitted call returning — async under JAX)
  tg3→tg4  device execution (until outputs are ready)
  tg4→tg5  device-to-host fetch of (small) results/metrics

`async_depth` is the TPU-idiomatic *Dynamic Pri*: with depth ≥ 2 the next
chunk is dispatched before the previous completes, so the device never waits
for the host thread to be rescheduled (the paper's O_td collapses). Depth 1
reproduces the paper's baseline Dynamic (synchronous clFinish()).

`priority_boost` is the literal paper optimization: raise the host/dispatch
thread's OS priority (best-effort `os.nice`; needs privileges to raise).

`JaxChunkExecutor` opens a telemetry scope (ring span + profiler annotation,
on the chunk's group track, ids group/seq) around `exec.inputs`
(make_inputs), and profiler annotations alone around `exec.h2d`
(device_put), `exec.wait` (the readiness wait) and `exec.fetch` (the
device-to-host fetch), whose intervals the chunk's record already puts in
the ring as its h2d/kernel/d2h phases; the step closure adds its own.
Two histograms split the dispatcher thread's CPU seconds per chunk
(`time.thread_time`): `exec.issue_cpu_s{group}` inside the step call, and
`exec.wait_cpu_s{group}` inside the wait for its outputs. Neither counts
the time the thread sleeps, so a wait that moves from the step call into
the readiness poll stays counted, and a wait that spins shows as CPU.
"""
from __future__ import annotations

import collections
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from repro import telemetry as telemetry_mod
from repro.core.types import Chunk, ChunkRecord, Token

clock = time.monotonic


class ChunkFailure(RuntimeError):
    """Raised by an executor when its device group dies mid-chunk."""


def try_boost_priority(delta: int = -10) -> bool:
    """Best-effort SetThreadPriority analogue. Lowering niceness requires
    privileges; returns whether the boost took effect."""
    try:
        os.nice(delta)
        return True
    except (PermissionError, OSError):
        return False


class ChunkExecutor:
    """Interface. execute() may complete earlier in-flight work; drain()
    flushes the pipeline at end-of-epoch.

    Executors are *reused across epochs* on the persistent scheduler
    runtime: on_worker_start() fires once per dispatcher thread (runtime
    lifetime), while drain() has per-epoch semantics — the dispatcher calls
    it when an epoch's space is exhausted so no in-flight work crosses an
    epoch boundary. abort() discards the pipeline after a group death and
    returns the abandoned chunks so the caller can requeue them."""

    def on_worker_start(self) -> None:
        pass

    def execute(self, token: Token, rec: ChunkRecord) -> List[ChunkRecord]:
        raise NotImplementedError

    def drain(self) -> List[ChunkRecord]:
        return []

    def cancel(self) -> List[ChunkRecord]:
        """Cooperative wind-down for epoch cancellation: return whatever
        already finished *without* waiting for the rest of the pipeline —
        still-running chunks stay in flight for ``abort()`` to hand back
        as requeue candidates. Synchronous executors have nothing in
        flight, so the default is a plain drain."""
        return self.drain()

    def abort(self) -> List[Chunk]:
        """Drop any in-flight work; returns the chunks to requeue."""
        return []

    def completed(self) -> List[ChunkRecord]:
        """Records that finished but were not yet returned when a failure
        interrupted execute()/drain(); the dispatcher collects them on the
        failure path so finished work is not discarded with the group."""
        return []


class CallableExecutor(ChunkExecutor):
    """Synchronous executor around fn(token) -> meta dict (or None)."""

    def __init__(self, fn: Callable[[Token], Optional[Dict]],
                 priority_boost: bool = False):
        self.fn = fn
        self.priority_boost = priority_boost
        self.boosted = False

    def on_worker_start(self) -> None:
        if self.priority_boost:
            self.boosted = try_boost_priority()

    def execute(self, token: Token, rec: ChunkRecord) -> List[ChunkRecord]:
        rec.tg1 = rec.tg2 = rec.tg3 = clock()
        meta = self.fn(token)
        rec.tg4 = rec.tg5 = clock()
        if meta:
            rec.meta.update(meta)
        return [rec]


class JaxChunkExecutor(ChunkExecutor):
    """Runs a jitted step on a JAX device group with measured offload phases.

    make_inputs(token) -> pytree of host (numpy) arrays for the chunk
    step(*device_inputs) -> outputs pytree (device)
    fetch(outputs) -> small host metrics (device-to-host phase)
    """

    #: bounded-backoff schedule for the readiness poll: a few free yields
    #: first (completion is usually imminent), then exponential sleeps
    #: capped so a long kernel costs at most POLL_MAX_S of detection lag
    POLL_MIN_S = 5e-5
    POLL_MAX_S = 1e-3

    def __init__(self, step: Callable, make_inputs: Callable[[Token], Any],
                 fetch: Optional[Callable[[Any], Any]] = None,
                 device=None, async_depth: int = 1,
                 priority_boost: bool = False,
                 completion_mode: str = "poll", telemetry=None):
        import jax
        if completion_mode not in ("poll", "block"):
            raise ValueError(f"completion_mode must be 'poll' or 'block', "
                             f"got {completion_mode!r}")
        self.jax = jax
        self.step = step
        self.make_inputs = make_inputs
        self.fetch = fetch or (lambda outs: None)
        self.device = device
        self.async_depth = max(1, async_depth)
        self.priority_boost = priority_boost
        self.completion_mode = completion_mode
        self.boosted = False
        self._inflight: Deque[Tuple[ChunkRecord, Any]] = collections.deque()
        self._lost_chunks: List[Chunk] = []       # popped, then failed
        self._pending_done: List[ChunkRecord] = []  # done, not yet returned
        # whether outputs carry a jax.Array.is_ready probe — decided on
        # the first dispatched output. On a jax too old to expose it,
        # "no probe" would read as "always ready" and the opportunistic
        # drain would block on every unfinished chunk (worse than the
        # depth-gated baseline), so poll mode degrades to block instead.
        self._poll_ok: Optional[bool] = None
        self.telemetry = telemetry_mod.resolve(telemetry)
        self._cpu_hists: Dict[Tuple[str, str], Any] = {}

    def _scope(self, name: str, token: Token):
        return telemetry_mod.scope(self.telemetry, name, token.group,
                                   group=token.group, seq=token.chunk.seq)

    def _annotate(self, name: str, token: Token):
        return telemetry_mod.annotate(self.telemetry, name,
                                      group=token.group, seq=token.chunk.seq)

    def _observe_cpu(self, name: str, group: str, seconds: float) -> None:
        h = self._cpu_hists.get((name, group))
        if h is None:
            h = self._cpu_hists[name, group] = \
                self.telemetry.registry.histogram(name, group=group)
        h.observe(seconds)

    def on_worker_start(self) -> None:
        if self.priority_boost:
            self.boosted = try_boost_priority()

    # -- event-driven completion ---------------------------------------
    def _polling(self) -> bool:
        return self.completion_mode == "poll" and bool(self._poll_ok)

    def _is_ready(self, outs: Any) -> bool:
        """Non-blocking readiness probe over the output pytree. Leaves
        without ``is_ready`` (host arrays, scalars) are always ready."""
        for leaf in self.jax.tree_util.tree_leaves(outs):
            is_ready = getattr(leaf, "is_ready", None)
            if is_ready is not None and not is_ready():
                return False
        return True

    def _wait_ready(self, outs: Any) -> None:
        """Wait for the chunk's outputs without parking the dispatcher in
        a hard ``block_until_ready``: poll ``jax.Array`` readiness with a
        bounded-backoff yield (the paper's anti-oversubscription wait —
        an oversubscribed host core gives its slice away instead of
        spinning). ``completion_mode="block"`` restores the synchronous
        wait (the paper's baseline Dynamic / benchmark old path)."""
        if not self._polling():
            self.jax.block_until_ready(outs)
            return
        delay = 0.0
        while not self._is_ready(outs):
            time.sleep(delay)       # 0.0 first: yield, don't nap
            delay = min(max(delay * 2.0, self.POLL_MIN_S), self.POLL_MAX_S)
        # all pollable leaves are ready: this returns without blocking and
        # covers any leaves that had no is_ready probe
        self.jax.block_until_ready(outs)

    def _complete_oldest(self, known_ready: bool = False) -> ChunkRecord:
        rec, outs = self._inflight.popleft()
        try:
            if self.telemetry is not None:
                cpu = time.thread_time()
            if known_ready:     # readiness just probed by the caller:
                # skip the poll loop, keep the no-op barrier for leaves
                # without a probe
                self.jax.block_until_ready(outs)
            else:
                with self._annotate("exec.wait", rec.token):
                    self._wait_ready(outs)
            if self.telemetry is not None:
                self._observe_cpu("exec.wait_cpu_s", rec.token.group,
                                  time.thread_time() - cpu)
            rec.tg4 = clock()
            with self._annotate("exec.fetch", rec.token):
                res = self.fetch(outs)
            rec.tg5 = clock()
        except BaseException:
            # the popped chunk is in neither _inflight nor the caller's
            # hands — remember it so abort() can hand it back for requeue
            self._lost_chunks.append(rec.token.chunk)
            raise
        # Tc3 (host resumed after completion) is stamped here, per record:
        # with async_depth ≥ 2 several records drain in one call, and a
        # single batch-level stamp would inflate O_td for all but the last
        rec.tc3 = clock()
        if res is not None:
            rec.meta["result"] = res
        return rec

    def execute(self, token: Token, rec: ChunkRecord) -> List[ChunkRecord]:
        done: List[ChunkRecord] = self._pending_done
        self._pending_done = []
        try:
            # opportunistic drain: anything already finished completes now
            # (no wait), so completion latency is hidden behind dispatch
            # instead of accumulating until the pipeline fills
            if self._polling():
                while self._inflight and self._is_ready(self._inflight[0][1]):
                    done.append(self._complete_oldest(known_ready=True))
            while len(self._inflight) >= self.async_depth:
                done.append(self._complete_oldest())
            with self._scope("exec.inputs", token):
                host_inputs = self.make_inputs(token)
            with self._annotate("exec.h2d", token):
                rec.tg1 = clock()
                dev_inputs = self.jax.device_put(host_inputs, self.device) \
                    if self.device is not None \
                    else self.jax.device_put(host_inputs)
            if self.telemetry is not None:
                cpu = time.thread_time()
            rec.tg2 = clock()
            outs = self.step(*dev_inputs) if isinstance(dev_inputs, tuple) \
                else self.step(dev_inputs)
            rec.tg3 = clock()                   # dispatch returned (async)
            if self.telemetry is not None:
                self._observe_cpu("exec.issue_cpu_s", token.group,
                                  time.thread_time() - cpu)
            if self._poll_ok is None:
                self._poll_ok = any(
                    hasattr(leaf, "is_ready")
                    for leaf in self.jax.tree_util.tree_leaves(outs))
            self._inflight.append((rec, outs))
            if self.async_depth == 1:
                done.append(self._complete_oldest())
        except BaseException:
            # a failure anywhere (completion OR launch of the new chunk)
            # must not discard records that already finished in this call
            self._pending_done = done
            raise
        return done

    def drain(self) -> List[ChunkRecord]:
        out = self._pending_done
        self._pending_done = []
        try:
            while self._inflight:
                out.append(self._complete_oldest())
        except BaseException:
            self._pending_done = out      # keep finished records visible
            raise
        return out

    def cancel(self) -> List[ChunkRecord]:
        """Cancellation wind-down: complete only the chunks whose outputs
        are already ready (free — no wait), leaving genuinely in-flight
        device work queued for ``abort()``/requeue. Without a readiness
        probe (block mode / old jax) there is no way to tell done from
        running, so fall back to a full drain — the submitted work is
        finishing on the device either way; draining just keeps its
        records instead of discarding real results."""
        out = self._pending_done
        self._pending_done = []
        try:
            if not self._polling():
                while self._inflight:
                    out.append(self._complete_oldest())
            else:
                while self._inflight \
                        and self._is_ready(self._inflight[0][1]):
                    out.append(self._complete_oldest(known_ready=True))
        except BaseException:
            self._pending_done = out      # keep finished records visible
            raise
        return out

    def abort(self) -> List[Chunk]:
        chunks = self._lost_chunks
        chunks += [rec.token.chunk for rec, _ in self._inflight]
        self._lost_chunks = []
        self._inflight.clear()
        return chunks

    def completed(self) -> List[ChunkRecord]:
        done, self._pending_done = self._pending_done, []
        return done


class SleepExecutor(ChunkExecutor):
    """Deterministic executor for scheduler unit tests: service time is
    chunk.size / rate plus fixed per-phase overheads. ``fail_after`` kills
    the group after N chunks; ``slow_after`` divides the rate by
    ``slow_factor`` after N chunks (a mid-run straggler)."""

    def __init__(self, rate: float, t_hd: float = 0.0, t_kl: float = 0.0,
                 t_dh: float = 0.0, fail_after: Optional[int] = None,
                 slow_after: Optional[int] = None, slow_factor: float = 10.0,
                 clock: Optional[Callable[[], float]] = None,
                 sleep: Optional[Callable[[float], None]] = None):
        self.rate = rate
        self.t_hd, self.t_kl, self.t_dh = t_hd, t_kl, t_dh
        self.fail_after = fail_after
        self.slow_after = slow_after
        self.slow_factor = slow_factor
        # injectable time source/sink: the deterministic test harness
        # (tests/clock.py VirtualClock) substitutes both so simulated
        # service time advances a virtual timeline instead of the wall
        self.clock = clock if clock is not None else globals()["clock"]
        self.sleep = sleep if sleep is not None else time.sleep
        self._count = 0

    def execute(self, token: Token, rec: ChunkRecord) -> List[ChunkRecord]:
        self._count += 1
        if self.fail_after is not None and self._count > self.fail_after:
            raise ChunkFailure(f"group {token.group} died")
        rate = self.rate
        if self.slow_after is not None and self._count > self.slow_after:
            rate = self.rate / self.slow_factor
        # skip zero-duration sleeps: time.sleep(0.0) is still a syscall
        # (~µs each, up to four per chunk), real overhead a *simulated*
        # run must not pay on its host-path measurements
        service = token.chunk.size / rate
        rec.tg1 = self.clock()
        if self.t_hd:
            self.sleep(self.t_hd)
        rec.tg2 = self.clock()
        if self.t_kl:
            self.sleep(self.t_kl)
        rec.tg3 = self.clock()
        if service:
            self.sleep(service)
        rec.tg4 = self.clock()
        if self.t_dh:
            self.sleep(self.t_dh)
        rec.tg5 = self.clock()
        return [rec]
