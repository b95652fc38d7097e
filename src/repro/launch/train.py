"""End-to-end heterogeneous training driver.

Example (CPU container — reduced config, ~100M-class training run):
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --reduced \\
      --steps 200 --global-batch 32 --seq-len 64 \\
      --groups accel:async=2,cpu:slow=2.5 --tune-chunk --ckpt-dir /tmp/ck

Groups syntax: name[:k=v,...] where kind is inferred (first group = accel),
knobs: async=<depth>, slow=<factor>, chunk=<fixed>, pri=1.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.checkpoint import Checkpointer
from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.core.types import DeviceKind
from repro.core.energy import EnergyModel, PowerSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.train.optimizer import OptConfig
from repro.train.trainer import GroupDef, HeteroTrainer


def parse_groups(spec: str):
    out = []
    for i, part in enumerate(spec.split(",")):
        bits = part.split(":")
        name = bits[0]
        kind = DeviceKind.ACCEL if i == 0 else (
            DeviceKind.LITTLE if name.startswith("little")
            else DeviceKind.BIG)
        g = GroupDef(name, kind)
        for kv in bits[1:]:
            k, v = kv.split("=")
            if k == "async":
                g.async_depth = int(v)
            elif k == "slow":
                g.slowdown = float(v)
            elif k == "chunk":
                g.fixed_chunk = int(v)
            elif k == "pri":
                g.priority_boost = bool(int(v))
        out.append(g)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--groups", default="accel:async=2,cpu0")
    ap.add_argument("--tune-chunk", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    groups = parse_groups(args.groups)
    oc = OptConfig(lr=args.lr, warmup_steps=args.warmup,
                   total_steps=args.steps)
    trainer = HeteroTrainer(cfg, groups, seq_len=args.seq_len,
                            global_batch=args.global_batch, oc=oc,
                            seed=args.seed)

    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck and args.resume and ck.latest_step() is not None:
        tree, meta = ck.restore()
        trainer.params = jax.tree.map(jax.numpy.asarray, tree["params"])
        trainer.opt = jax.tree.map(jax.numpy.asarray, tree["opt"])
        trainer.step_idx = meta["step"]
        print(f"resumed from step {meta['step']}")

    if args.tune_chunk:
        G = trainer.tune_accel_chunk()
        print(f"tuned accel chunk G = {G}")

    energy = EnergyModel({g.name: PowerSpec(200.0, 75.0) for g in groups})
    t0 = time.time()
    while trainer.step_idx < args.steps:
        rep = trainer.train_step()
        acc_ov = rep.overheads.get(groups[0].name, {})
        print(f"step {rep.step:4d} loss {rep.loss:.4f} "
              f"({rep.time_s:.2f}s, items {rep.per_group_items}, "
              f"O_td {acc_ov.get('O_td', 0) * 100:.1f}%)", flush=True)
        if ck and rep.step % args.ckpt_every == 0:
            ck.save_async(rep.step,
                          {"params": trainer.params, "opt": trainer.opt})
    if ck:
        ck.wait()
        ck.save(trainer.step_idx,
                {"params": trainer.params, "opt": trainer.opt})
    wall = time.time() - t0
    busy = {}
    for rep in trainer.history:
        for g, n in rep.per_group_items.items():
            busy[g] = busy.get(g, 0.0) + n * 1e-3
    erep = energy.energy(wall, busy)
    print(json.dumps({"wall_s": wall, "final_loss": trainer.history[-1].loss,
                      "energy_model_j": erep.total_j, "edp": erep.edp}))


if __name__ == "__main__":
    enable_compile_cache()
    main()
