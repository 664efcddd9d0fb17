"""Find an open-loop cell's knee: the highest offered job rate at which
the load generator keeps up through the window.

    python bench/sweep.py --workload <cell> --rates 20,30,40 --seconds 10 --seed <n>

One process runs one window per rate (the same seed, so the same jobs)
and prints, per rate, the latency percentiles and how late the steps
were submitted in the first and the last third of the window.  A rate
is sustained while the late share stays small and the lag does not grow
from the first third to the last.  The chosen cell rate is written into
the mix's file by hand, with the sweep recorded in ``PERF.md``.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time


def main(argv=None) -> int:
    import argparse

    import numpy as np

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        sink: dict = {}
        out = harness.run(
            args.workload, args.seed, args.seconds, False, time.perf_counter(),
            rate=rate, sink=sink,
        )
        lags = np.array(sink["rec"]["gen_lags_s"]) * 1e3
        third = max(1, len(lags) // 3)
        row = {
            "rate_jobs_per_s": rate,
            "correct": out["correct"],
            "jobs": out["attempted"],
            "unanswered": out["failed"],
            "p50_ms": out["metrics"].get("place_p50_ms", {}).get("value"),
            "p95_ms": out["metrics"].get("place_p95_ms", {}).get("value"),
            "lag_p95_ms": float(np.percentile(lags, 95)),
            "lag_first_third_max_ms": float(lags[:third].max()),
            "lag_last_third_max_ms": float(lags[-third:].max()),
            "late_share": float((lags > 1.0).mean()),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import sweep

    sys.exit(sweep.main())
