#!/usr/bin/env python3
"""Smoke run on the chip: serve stablelm-1.6b at its published widths.

Drives the queued serve path once through its own entry point,
``repro.launch.serve.main``, in this process: admission, the DWRR tenant
drain, the persistent scheduler, ``JaxChunkExecutor`` and greedy
prefill+decode on a TPU, with weights made from the seed. It then checks
what came out: every job done, no device group lost, the queue drained,
every generated token a vocabulary id, every chunk's output on a TPU, and,
for one chunk of each group, tokens equal to a direct greedy
prefill+decode of the same prompts on the same device, at the same padded
batch shape, outside the scheduler.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four runtimes, one per chip, and
                                       # the same jobs on one runtime
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny model, no chip

The times it prints come from a smoke run, compilation included, and are
not a benchmark. Only when every check passed on a TPU does it print, as
its last line, ``{"ok": true, "device": {"platform", "kind", "count"}}``;
any failure exits nonzero without that line. ``--rehearse`` serves the
``--reduced`` config on whatever backend JAX has and never prints it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "stablelm-1.6b"
SEED = 0
PROMPT_LEN = 128
DECODE_TOKENS = 32
GROUPS = ("accel", "cpu0")          # serve.py's default --groups
SERVE_ARGV = ["--arch", ARCH, "--queue", "--requests", "64",
              "--job-items", "2", "--prompt-len", str(PROMPT_LEN),
              "--decode-tokens", str(DECODE_TOKENS), "--seed", str(SEED),
              "--tenants", "gold:weight=4,free:weight=1"]
FOUR = 4


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Records JAX's own trace, lowering and backend-compile durations,
    from any thread, with the time each ended, and counts
    persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.ended = []              # (perf_counter at end, seconds)
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def seconds(self, start: float, end: float) -> float:
        """Compile seconds of the events that ended in [start, end)."""
        with self._lock:
            return sum(d for t, d in self.ended if start <= t < end)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            with self._lock:
                self.ended.append((time.perf_counter(), duration))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def serve(extra, clock: CompileClock, label: str) -> dict:
    """One run of the serve entry point; prints its set-up, compile and
    serve times."""
    from repro.launch import serve as serve_mod
    h0 = clock.cache_hits
    t0 = time.perf_counter()
    rep = serve_mod.main(SERVE_ARGV + list(extra))
    t2 = time.perf_counter()
    t1 = t2 - rep["time_s"]          # about when the first job went in
    print(f"[{label}] set-up s (weights from seed, engine build): "
          f"{t1 - t0:.3f}, of which compile s {clock.seconds(t0, t1):.3f}")
    print(f"[{label}] compile s in the serve (JAX trace + lower + XLA "
          f"compile, summed over threads): {clock.seconds(t1, t2):.3f}; "
          f"persistent-cache hits in set-up and serve: "
          f"{clock.cache_hits - h0}")
    print(f"[{label}] smoke run, not a benchmark: {rep['new_tokens']} "
          f"tokens served in {rep['time_s']} s = {rep['tok_per_s']} tok/s "
          f"(compilation included)")
    for g, o in rep["outputs"].items():
        print(f"[{label}] group {g}: outputs on {', '.join(o['devices'])} "
              f"({o['chunks']} chunks)")
    return rep


def check_report(rep: dict, groups, platform: str, vocab: int) -> None:
    """Every job done and drained, no group lost, outputs only from the
    expected ``groups``, token ids in the vocabulary, outputs on
    ``platform``."""
    check(rep["done"] == rep["jobs"],
          f"{rep['done']} of {rep['jobs']} jobs done")
    check(rep["drained"], "the queue did not drain")
    check(not rep.get("dead_groups"),
          f"device groups died: {rep.get('dead_groups')}")
    outputs = rep["outputs"]
    check(outputs and set(outputs) <= set(groups),
          f"groups with outputs {sorted(outputs)}, expected "
          f"{sorted(groups)}")
    for g, o in outputs.items():
        lo, hi = o["token_range"]
        check(0 <= lo and hi < vocab,
              f"{g}: generated token ids span [{lo}, {hi}], vocab {vocab}")
        off = [d for d in o["devices"] if not d.startswith(platform + ":")]
        check(not off, f"{g}: outputs on non-{platform} devices {off}")


def check_reference(rep: dict, ref_eng) -> None:
    """Each group's sampled chunk against a direct greedy prefill+decode
    of the same prompts, on the device its outputs lived on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    cfg = ref_eng.cfg

    @jax.jit
    def prefill(params, tokens):
        return M.prefill(cfg, params, tokens, None, max_len=ref_eng.max_len)

    @jax.jit
    def decode(params, cache, tokens):
        return M.decode_step(cfg, params, cache, tokens)

    devices = {f"{d.platform}:{d.id}": d for d in jax.devices()}
    params_on = {}
    for g, o in sorted(rep["outputs"].items()):
        check(len(o["devices"]) == 1,
              f"{g}: outputs on several devices {o['devices']}")
        device = devices[o["devices"][0]]
        if device not in params_on:
            params_on[device] = jax.device_put(ref_eng.params, device)
        params = params_on[device]
        rows = o["sample"]["rows"]
        got = np.asarray(o["sample"]["tokens"], np.int32)
        prompts = np.zeros((got.shape[0], PROMPT_LEN), np.int32)
        prompts[:len(rows)] = np.stack([ref_eng._prompt(i) for i in rows])
        logits, cache = prefill(params, jax.device_put(prompts, device))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        toks = [tok]
        for _ in range(DECODE_TOKENS - 1):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            toks.append(tok)
        want = np.asarray(jnp.concatenate(toks, axis=1))
        check(got.shape == want.shape,
              f"{g}: sampled tokens {got.shape}, reference {want.shape}")
        check(np.array_equal(got, want),
              f"{g}: rows {rows} differ from a direct greedy decode at "
              f"{int((got != want).sum())} of {want.size} tokens")
        print(f"reference: {g} rows {rows[0]}..{rows[-1]} (batch "
              f"{got.shape[0]}) on {o['devices'][0]}: tokens equal to a "
              f"direct greedy prefill+decode")


def device_bytes(devices, stat: str) -> str:
    """``memory_stats()[stat]`` of each device."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or stat not in s for s in stats):
        return "not reported by this backend"
    return ", ".join(f"{d.platform}:{d.id}={s[stat]}"
                     for d, s in zip(devices, stats))


def collect(devices, label: str) -> None:
    """Free what the last phase left (the engine is a reference cycle)
    and print the device bytes in use before and after, and the peak so
    far."""
    before = device_bytes(devices, "bytes_in_use")
    gc.collect()
    print(f"[{label}] device bytes in use: {before}; after freeing the "
          f"engine: {device_bytes(devices, 'bytes_in_use')}; peak so far: "
          f"{device_bytes(devices, 'peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-chip phase: --runtimes 4 (one "
                         "runtime per chip) against --runtimes 1 on chip 0")
    ap.add_argument("--rehearse", action="store_true",
                    help="serve the --reduced config on any backend; never "
                         "prints the ok line")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if not args.rehearse:
        check(dev.platform == "tpu",
              f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
    if args.four_chips:
        check(len(devices) == FOUR,
              f"--four-chips needs {FOUR} devices, found {len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import HeteroServeEngine

    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    cfg = get_config(ARCH)
    extra = []
    if args.rehearse:
        cfg = reduced(cfg)
        extra = ["--reduced"]

    reports = []
    if args.four_chips:
        one = serve(extra + ["--runtimes", "1"], clock, "1 runtime, chip 0")
        check_report(one, GROUPS, dev.platform, cfg.vocab)
        reports.append(one)
        collect(devices, "1 runtime, chip 0")
        fed = serve(extra + ["--runtimes", str(FOUR)], clock,
                    f"{FOUR} runtimes")
        check_report(fed, [f"r{k}/{g}" for k in range(FOUR) for g in GROUPS],
                     dev.platform, cfg.vocab)
        used = {d for o in fed["outputs"].values() for d in o["devices"]}
        check(len(used) == FOUR,
              f"{FOUR} runtimes produced outputs on {sorted(used)}")
        reports.append(fed)
        collect(devices, f"{FOUR} runtimes")
        print(f"smoke run, not a benchmark: 1 runtime on chip 0 "
              f"{one['tok_per_s']} tok/s; {FOUR} runtimes on {FOUR} chips "
              f"{fed['tok_per_s']} tok/s")
    else:
        reports.append(serve(extra, clock, "1 chip"))
        check_report(reports[0], GROUPS, dev.platform, cfg.vocab)
        check(len(reports[0]["outputs"]) == len(GROUPS),
              f"groups with outputs {sorted(reports[0]['outputs'])}, "
              f"expected all of {GROUPS}")
        collect(devices, "1 chip")
    print(f"peak device bytes in use, serving: "
          f"{device_bytes(devices, 'peak_bytes_in_use')}")

    ref_eng = HeteroServeEngine(cfg, [], prompt_len=PROMPT_LEN,
                                decode_tokens=DECODE_TOKENS, seed=SEED)
    for rep in reports:
        check_reference(rep, ref_eng)
    print(f"peak device bytes in use, serving and reference: "
          f"{device_bytes(devices, 'peak_bytes_in_use')}")

    if args.rehearse:
        print("rehearsal passed; it is not a chip run and prints no result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip smoke failed: {e}", file=sys.stderr)
        sys.exit(1)
