"""Readings that set a cell's correctness limit: the program's widest
logit gap and the fp8 control's, on the same served sequences, over many
seeds in one process (one set-up of the chip for all of them).

    python3 -m chipbench.control --workload <cell> --seconds 4 \\
        --seeds 2147483801,2147483802,...

Prints one JSON line per seed: the program's widest gap and
``correct``, and the control's widest gap and ``control_correct``, the
control put in the program's place and judged at the cell's own limits.
Then the lower reading (the largest program gap), the upper reading (the
smallest control gap) and whether any control run came out correct. The
benchmark's own runs never read the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench.harness import run_cell
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(args.workload, seed, args.seconds, False,
                       time.perf_counter(), rehearse=args.rehearse,
                       control=True)
        row = {"seed": seed, "correct": out["correct"], **out["control"],
               "device": out["device"]["kind"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower": max(r["program_gap"] for r in rows),
        "upper": min(r["control_gap"] for r in rows),
        "control_ever_correct": any(r["control_correct"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
