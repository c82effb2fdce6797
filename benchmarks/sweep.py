"""Find the knee of an open-loop cell: several rates in one process after
one set-up.  A tool for a ``benchmark`` PR, run once on the chip; its
result is written into the traffic file as ``rate_rps`` by hand.

    python3 benchmarks/sweep.py --workload <name> --rates 4,6,8,10,12 [--seconds 20] [--out file]

The knee is the highest rate at which the backlog (requests sent and not
finished) at the end of the window is no larger than at its middle.
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    from benchmarks.harness import cell as harness
    from benchmarks.harness.stats import END_TO_END

    session = harness.set_up(args.workload, args.seed, trace=False)
    rows = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(session.cell.traffic, rate_rps=rate)
            # A seed of its own for each rate: the same seed would offer prompts
            # that begin alike, hit the prefix cache and compile the chunk programs.
            m = harness.measure(session, traffic, args.seed + i, args.seconds,
                                sample_series=True)
            mid = m.window.t0 + args.seconds / 2
            backlog_mid = min(m.samples, key=lambda s: abs(s["t"] - mid))["in_flight"]
            backlog_end = m.samples[-1]["in_flight"]
            row = {"rate_rps": rate, "sample": len(m.window.sample),
                   "failed": sum(not r.ok for r in m.window.sample),
                   "backlog_mid": backlog_mid, "backlog_end": backlog_end,
                   "lanes_mean": sum(s["active_slots"] for s in m.samples) / len(m.samples),
                   "drained_s": m.drained_s,
                   "compiles_in_window": m.counters["compiles_in_window"]}
            for name, fn in END_TO_END.items():
                row[name] = fn(m.window)
            rows.append(row)
            print(json.dumps(row), flush=True)
            deadline = time.monotonic() + 120.0  # let the backlog clear before the next rate
            while session.engine.has_work and time.monotonic() < deadline:
                time.sleep(0.1)
    finally:
        session.close()
    steady = [r["rate_rps"] for r in rows if r["backlog_end"] <= r["backlog_mid"]]
    summary = {"workload": args.workload, "seconds": args.seconds, "rows": rows,
               "knee_rps": max(steady) if steady else None}
    print(json.dumps(summary))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
