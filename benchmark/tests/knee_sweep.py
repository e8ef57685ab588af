"""The sweep that finds a serving cell's knee, once, on the chip: one server
boot, one open-loop window at each fixed rate.

    python3 benchmark/tests/knee_sweep.py <workload> --rates 1,2,3 [--seconds 20]

The knee is the highest rate at which the backlog does not grow through the
window; the workload files then carry it (``knee_pairs_per_s``) beside the
rate set from it (PERF.md section 4, Knee).
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2147483777)
    args = ap.parse_args(argv)
    import numpy as np

    from benchmark import harness
    from benchmark.entries.serve_http import Server

    cell = harness.load_cell(args.workload)
    server = Server(cell, args.seed)
    try:
        server.wait_ready()
        for rate in (float(r) for r in args.rates.split(",")):
            w = server.window(rate, args.seconds, args.seed)
            res, n = w["res"], w["n"]
            done = res.done[res.ok]
            half = args.seconds / 2
            lat = res.latency_s * 1e3
            first = lat[res.due[res.ok] < half]
            second = lat[res.due[res.ok] >= half]
            batches = [i[1] for i, ok in zip(res.info, res.ok) if ok]
            print(f"rate {rate:.2f}/s: offered {n}, answered "
                  f"{int(res.ok.sum())}, in window "
                  f"{int((done <= args.seconds).sum())} "
                  f"({(done <= args.seconds).sum() / args.seconds:.3f}/s), "
                  f"backlog at window end {n - int((done <= args.seconds).sum())}, "
                  f"latency ms p50 {np.median(lat):.0f} p95 "
                  f"{np.percentile(lat, 95):.0f} max {lat.max():.0f}; mean "
                  f"latency first half {first.mean():.0f} second half "
                  f"{second.mean():.0f}; mean batch "
                  f"{np.mean(batches):.2f}; generator late p95 "
                  f"{np.percentile(res.late_s * 1e3, 95):.1f} ms",
                  flush=True)
        server.stop()
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
