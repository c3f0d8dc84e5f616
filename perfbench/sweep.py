"""Find an open-loop cell's knee once: one replica, several fixed rates.

    python3 perfbench/sweep.py --workload serve-chat-steady \\
        --rates 0.3,0.45,0.6,0.75,0.9,1.1 --seconds 35 --seed 1

Prints one JSON line per rate (raw points for PERF.md). The knee is the
highest rate at which the backlog does not grow over the window; the cell's
fixed rate (0.8 x knee) is then a number in its traffic file. Not part of a
run: the driver never calls this.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main():
    from perfbench import stats
    from perfbench.manifest import Manifest
    from perfbench.runners import serve as runner

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=35.0)
    ap.add_argument("--lead-in", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    man = Manifest(ROOT)
    cell = man.cell(args.workload)
    mix = man.traffic(cell["traffic"])
    sess = runner.ServeSession(man, cell, args.seed, args.rehearse)
    try:
        sess.deploy()
        sess.warm_up(mix)
        for rate in (float(r) for r in args.rates.split(",")):
            m = {**mix, "rate_per_s": rate, "lead_in_s": args.lead_in}
            run = asyncio.run(runner.open_loop(sess, m, args.seconds,
                                               args.seed, False))
            s = runner.summarise(sess, m, run)
            measured = sorted((x for x in run["sent"] if x.req.measured
                               and x.times), key=lambda x: x.due)
            half = len(measured) // 2
            ttft = lambda xs: [(x.times[0] - x.due) * 1e3 for x in xs]  # noqa: E731
            print(json.dumps({
                "rate_per_s": rate, "seconds": args.seconds,
                "requests": s["attempted"], "failed": s["failed"],
                "backlog_at_open": run["backlog_at_open"],
                "backlog_at_close": run["backlog_at_close"],
                "drain_s": round(run["drain_s"], 2),
                "ttft_ms_median_first_half": round(stats.median(
                    ttft(measured[:half])), 1) if half else None,
                "ttft_ms_median_second_half": round(stats.median(
                    ttft(measured[half:])), 1) if measured else None,
                "ttft_ms_p95": round(stats.percentile(s["ttft_ms"], 95), 1)
                if s["ttft_ms"] else None,
                "gap_ms_median": round(stats.median(s["gaps_ms"]), 2)
                if s["gaps_ms"] else None,
                "gap_ms_p99": round(stats.percentile(s["gaps_ms"], 99), 1)
                if s["gaps_ms"] else None,
                "out_tokens_per_s": round(s["out_tokens"] / s["seconds"], 1),
                "compiles_in_window": run["compiles_in_window"],
                "device": sess.info["device"]["device_kind"]}), flush=True)
            t0 = time.perf_counter()   # let the engine empty before the next
            while time.perf_counter() - t0 < 120:
                info = sess.admin("bench_info")
                if not info["active_slots"] and not info["pending"]:
                    break
                time.sleep(0.5)
    finally:
        sess.close()


if __name__ == "__main__":
    main()
