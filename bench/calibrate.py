"""Readings that the check's limits are set from, for one cell, many seeds.

    python3 bench/calibrate.py --workload iterate.mixed_sizes --seconds 5 \
        --seeds 101 102 103 --out readings.jsonl

Each seed is one short run of the cell in this process (set-up, a short
window at the cell's own load, the check), with, beside the program's
numbers, the readings of the control (the reference in the program's
place, computed one precision lower) and of the planted faults.  One JSON
line per seed goes to ``--out`` and to standard output; the last line is
the largest reading of each number over the seeds, per variant.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def summarize(lines: list[dict]) -> dict:
    """Per variant (``program`` and each reading), each number's largest
    and smallest value over the seeds."""
    out: dict = {}
    for line in lines:
        variants = {"program": {k: c["value"] for k, c in line["checks"].items()},
                    **line["readings"]}
        for variant, numbers in variants.items():
            for k, v in numbers.items():
                lo, hi = out.setdefault(variant, {}).get(k, (v, v))
                out[variant][k] = (min(lo, v), max(hi, v))
    return {v: {k: {"min": lo, "max": hi} for k, (lo, hi) in ns.items()} for v, ns in out.items()}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    lines = []
    with open(args.out, "a") as f:
        for seed in args.seeds:
            t0 = time.monotonic()
            try:
                r = harness.run_cell(args.workload, seed, args.seconds, False, t0=t0,
                                     calibrate=True)
            except harness.NoChip as e:
                print(f"calibrate: {e}", file=sys.stderr)
                return 3
            line = {"workload": args.workload, "seed": seed, "correct": r["correct"],
                    "device": r["device"], "checks": r["checks"], "readings": r["readings"]}
            lines.append(line)
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(lines),
                      "summary": summarize(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
