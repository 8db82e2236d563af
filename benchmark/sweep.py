"""The knee sweep: one server, one index, open-loop rates up a x1.25 ladder.

    python benchmark/sweep.py --workload <open-loop cell> --seed <n> [--first 50]
        [--steps 14] [--seconds 10]

Run once, by hand, on the chip. The knee is the highest rate at which at least 99% of
the searches due in the window complete in it, the second half's p95 is within 1.5x of
the first half's, and p95 is within 4x of the lowest rate's. The cell's mix then gets
four fifths of it as `rate_per_s`, written into the mix's file as a number, and the
table goes into PERF.md. The benchmark itself never searches for a rate.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--first", type=float, default=50.0)
    ap.add_argument("--steps", type=int, default=14)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--docs", type=int, default=None)
    args = ap.parse_args(argv)
    args.trace = 0
    run = cell.Run(args, T_PROCESS)
    if run.mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    try:
        run.start()
        run.make_corpus()
        run.server.wait_started()
        run.device()
        run.ingest()
        run.make_reference()
        run.first_answers()
        rates = [args.first * 1.25 ** i for i in range(args.steps)]
        # warm up at a middle rate: the pool once, then rehearsals
        run.mix["rate_per_s"] = rates[len(rates) // 2]
        run.warm_up()
        rows = []
        for r in rates:
            run.mix["rate_per_s"] = r
            run.plan = None  # a new schedule at this rate
            c0 = run.stats()["device"]["compile"]["total"]
            res = run.load()
            c1 = run.stats()["device"]["compile"]["total"]
            due, sent, done, ok = res.arrays()
            lat = (done - due) * 1000.0
            half = due < args.seconds / 2
            row = {"phase": "sweep", "rate_per_s": round(r, 1), "due": len(due),
                   "completed_in_window_share":
                       float((ok & (done <= args.seconds)).mean()),
                   "failed": int((~ok).sum()),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "p95_first_half_ms": float(np.percentile(lat[half], 95)),
                   "p95_second_half_ms": float(np.percentile(lat[~half], 95)),
                   "late_p95_ms": float(np.percentile((sent - due) * 1000.0, 95)),
                   "generator_cpu_share": round(res.cpu_s / res.wall_s, 3),
                   "compile_events": c1 - c0}
            rows.append(row)
            cell.say(row)
            if row["completed_in_window_share"] < 0.9:
                break  # far past the knee: the queue only grows from here
        base = rows[0]["p95_ms"]
        good = [row["rate_per_s"] for row in rows
                if row["completed_in_window_share"] >= 0.99
                and row["p95_second_half_ms"] <= 1.5 * row["p95_first_half_ms"]
                and row["p95_ms"] <= 4 * base]
        knee = max(good) if good else None
        cell.say({"phase": "knee", "knee_per_s": knee,
                  "four_fifths": None if knee is None else round(0.8 * knee, 1)})
        return 0
    finally:
        if run.server is not None:
            run.server.stop()
            shutil.rmtree(run.run_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
