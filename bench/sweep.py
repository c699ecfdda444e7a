"""Find a cell's knee: the highest offered rate at which the served path
keeps up.

    python3 bench/sweep.py --workload <cell> --seed <n> --step-seconds <s> \\
        --gap-seconds <g> --rates 10,20,30,40

One process, one set-up, one schedule: a step of ``--step-seconds`` at each
rate (gR-Tx plus gRW-Tx per second, in this order), each followed by
``--gap-seconds`` with no arrivals, so that every step starts with the
backlog of the one before it drained. A step keeps up when every request
due in it is served by the end of the first loop iteration that ends after
the step, and the backlog (due, not yet served) at its end is no longer
than at its start. Prints one line per step and, last, the JSON list of
steps.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def steps(sched, out, rates, seconds, gap_s):
    import numpy as np

    from bench.graphgen import READ, WRITE, step_phases

    win = np.arange(sched.n_warmup, len(sched))
    due, done = sched.due[win], out.done[win]
    lat = (done - due) * 1e3
    rows = []
    for r, (lo, hi, _) in zip(rates, step_phases(seconds, rates, gap_s)):
        sel = (due >= lo) & (due < hi)
        reads = sel & (sched.kind[win] == READ)
        writes = sel & (sched.kind[win] == WRITE)
        # the end of the first loop iteration that ends after the step
        ends = [t for t in out.iter_ends if t >= hi]
        cut = ends[0] if ends else hi
        backlog = lambda t: int(np.sum((due < t) & ~(done <= t)))
        row = {
            "rate_per_s": r, "requests": int(sel.sum()),
            "served_by_cut": float(np.mean(done[sel] <= cut)) if sel.any() else 1.0,
            "backlog_start": backlog(lo), "backlog_end": backlog(hi),
            "gr_p50_ms": float(np.nanpercentile(lat[reads], 50)),
            "gr_p99_ms": float(np.nanpercentile(lat[reads], 99)),
            "grw_p50_ms": float(np.nanpercentile(lat[writes], 50))
            if writes.any() else None,
            "max_ms": float(np.nanmax(lat[sel])) if sel.any() else None,
        }
        row["keeps_up"] = (row["served_by_cut"] == 1.0
                           and row["backlog_end"] <= row["backlog_start"])
        rows.append(row)
    return rows


def sweep(cell, seed, rates, step_s, gap_s, *, t_start, log):
    """Run the sweep on ``cell``; returns ``(rows, result)``."""
    from bench import harness

    seconds = (step_s + gap_s) * len(rates)
    cell.traffic["arrivals"] = {"process": "steps", "gap_s": gap_s,
                                "rates_x": [r / rates[0] for r in rates]}
    cell.rate = rates[0]
    got = {}
    res = harness.run(cell, seed, seconds, False, t_start=t_start, log=log,
                      on_window=lambda s, o: got.update(s=s, o=o))
    rows = steps(got["s"], got["o"], rates, seconds, gap_s)
    for r in rows:
        log(" ".join(f"{k}={v}" for k, v in r.items()))
    log(f"correct={res['correct']} compared={json.dumps(res['compared'])}")
    return rows, res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--step-seconds", type=float, required=True)
    ap.add_argument("--gap-seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]

    from bench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    rows, _ = sweep(harness.load_cell(args.workload), args.seed, rates,
                    args.step_seconds, args.gap_seconds, t_start=T_START, log=log)
    print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
