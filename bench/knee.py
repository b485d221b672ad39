"""Sweep an open-loop serving cell over fixed rates, to find its knee once.

  python3 bench/knee.py --workload <open-loop cell> --seed 5 --seconds 10 \
      --rates 1000 2000 2500 3000

The cell is one of BENCHMARK.json whose traffic mix has ``"loop": "open"``.
One set-up, then one window per rate, each printed as a JSON line: requests,
failures, latency percentiles, the p50 of the requests due in the first and in
the last fifth of the window, and the generator's lag. The knee is the highest
rate at which nothing fails, the last fifth's p50 stays with the first fifth's
and the lag stays flat: past it the backlog grows through the window.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import numpy as np  # noqa: E402

from bench import cells  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    cell = cells.load(args.workload)
    from bench.jobs.serve import Job, nearest_rank
    from bench.run import configure_jax

    if configure_jax().devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 3
    job = Job(cell, args.seed, args.seconds, traced=False)
    job.setup(queries=int(max(args.rates) * args.seconds * 1.1) + 1000)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in args.rates:
        job.window(None, rate=rate)
        n = job.count
        lat, due = job.latency_ms, job.due[:n] - job.t0
        lag = (job.sent[:n] - job.due[:n]) * 1e3
        first, last = due < args.seconds / 5, due >= args.seconds * 4 / 5
        print(json.dumps({
            "rate_rps": rate, "requests": int(n), "failed": int(job.failed),
            "p50_ms": nearest_rank(lat, 50), "p95_ms": nearest_rank(lat, 95),
            "p99_ms": nearest_rank(lat, 99),
            "first_fifth_p50_ms": nearest_rank(lat[first], 50),
            "last_fifth_p50_ms": nearest_rank(lat[last], 50),
            "lag_p99_first_fifth_ms": nearest_rank(lag[first], 99),
            "lag_p99_last_fifth_ms": nearest_rank(lag[last], 99),
            "within_limit": float(np.mean(lat <= cell.traffic["latency_limit_ms"])),
            "occupancy": job.stats["batch_rows_real"] / max(job.stats["batch_rows_padded"], 1),
            "cache_hits": job.stats["cache_hits"], "gc": job.gc.summary(),
        }), flush=True)
    job.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
