"""ftbench's command: one cell, one run, one result line.

    python3 ftbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--rehearse`` walks the same cell on the CPU at toy widths (set
``JAX_PLATFORMS=cpu`` and as many virtual devices as the cell has chips) and
prints no metric.  Without it a machine with no TPU, or with fewer chips
than the cell asks for, is exit code 1 and no result.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    from ftbench import harness, spec

    cell = spec.load_cell(args.workload)
    return harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), args.rehearse, T_PROCESS
    )


if __name__ == "__main__":
    sys.exit(main())
