"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, prints one JSON object as the
last line of standard output and exits.  Without the chips the cell asks
for it fails (exit 2, no result line); it never falls back to the CPU.
"""

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        parser.error("--seed must be >= 0 and --seconds > 0")

    from benchmarks.harness.cell import NoAccelerator, run_cell

    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_process=T_PROCESS)
    except NoAccelerator as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Not sys.exit: tearing the TPU runtime down after the step thread has
    # stopped segfaulted two runs in five on the chip, after a good result.
    os._exit(code)
