"""Readings for setting the benchmark's limits and rates, many seeds in one
process (one chip, one warm compile cache):

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--control] [--rates 3,4,5] [--no-check] [--trace 0|1]

For each seed it runs the cell as ``run.py`` does and prints one JSON line:
the result, with ``program_gap`` beside it under ``--control``, where the
float8 control's first choices take the served tokens' place and decide
``correct``. ``--rates`` replaces an open-loop mix's arrival rate, one rate
after another (the sweep that finds the knee), and adds the queue's
readings under ``observed``; ``--no-check`` skips the reference. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent / p) for p in ("", "src")]

from chipbench import core  # noqa: E402
from chipbench.run import NoChip, run_cell  # noqa: E402


def queue_readings(run):
    """Whether an open loop kept up: tails of the time to first token and
    of the queue wait, the share finished, and the median time to first
    token of the last third of arrivals over that of the first third (a
    queue that grows through the window reads well over 1)."""
    w = run.window
    reqs = sorted(w.requests, key=lambda r: r.ledger.arrival_s)
    end = lambda t: t if t is not None else w.closed_s  # noqa: E731
    ttft = [end(r.ledger.first_token_s) - r.ledger.arrival_s for r in reqs]
    wait = [end(r.ledger.admitted_s) - r.ledger.arrival_s for r in reqs]
    third = max(1, len(reqs) // 3)
    ms = lambda xs, q: 1e3 * core.percentile(xs, q)  # noqa: E731
    return {"ttft_p50_ms": ms(ttft, 50), "ttft_p95_ms": ms(ttft, 95),
            "ttft_p99_ms": ms(ttft, 99), "queue_p50_ms": ms(wait, 50),
            "queue_p95_ms": ms(wait, 95),
            "finished_share": sum(r.done for r in reqs) / len(reqs),
            "ttft_growth": (core.percentile(ttft[-third:], 50)
                            / core.percentile(ttft[:third], 50))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default=None,
                    help="comma-separated arrival rates, each run on every seed")
    ap.add_argument("--no-check", action="store_true")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    man = core.manifest()
    base = core.load_mix(core.cell(man, args.workload)["traffic"])
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [base.get("rate_rps")])
    for rate in rates:
        mix = dict(base, rate_rps=rate) if rate is not None else base
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            try:
                res = run_cell(args.workload, seed, args.seconds, bool(args.trace),
                               mix=mix, control=args.control, t_start=t0,
                               check=not args.no_check,
                               observe=queue_readings if args.rates else None)
            except NoChip as e:
                return int(e.code)
            res.update(seed=seed, rate_rps=rate, run_s=time.perf_counter() - t0)
            print("CALIBRATE " + json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
