"""Run one benchmark cell on the chips this process finds.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` when traced, and last ``limits``: each number the check
compared, with its limit. The same pairs end standard error. Exits 2,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for, and 1 when the cell cannot be resolved or the program cannot
be imported.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the program's tiny same-family config on any "
                         "backend; prints no result line")
    args = ap.parse_args(argv)

    from chipbench.harness import NoChip, run_cell
    from chipbench.spec import SpecError
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_PROCESS, rehearse=args.rehearse)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    except (SpecError, ImportError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for name, (value, limit) in out["limits"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        print(f"rehearsal, not a chip run: {json.dumps(out)}",
              file=sys.stderr)
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
