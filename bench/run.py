"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload iterate.mixed_sizes --seed 7 --seconds 42 --trace 0

The last line of standard output is the result (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last the ``checks``: each number compared with its
limit); the last lines of standard error repeat the checks.  With no TPU,
or fewer chips than the cell asks for, it exits 3 and prints no result.
"""

import time

T0 = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t0=T0
        )
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
