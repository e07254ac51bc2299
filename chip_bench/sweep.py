#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one set-up, then one window per rate.

    python3 chip_bench/sweep.py --workload cop20k_A_synth.open1 --seed 11 --seconds 10 --rates 10,14,18,22,26

For each rate the mix is run as written except for ``rate_per_s``.  Each
line gives the offered and the completed rate, the median and 95th
percentile latency, and the backlog growth: the median latency of the last
quarter of the requests over that of the first quarter, which stays near 1
while the system keeps up.  The last line holds the check of every answer.
The benchmark's cells never run this; it sets an open-loop mix's rate
once, for a cell named in ``BENCHMARK.json``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)

    import numpy as np
    from chip_bench import cells, harness, run, traffic
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    batch_width = cells.reader("router.batch_width")
    bench = cells.Benchmark.load(ROOT)
    cell = harness.Cell(bench, args.workload,
                        run.require_chips(int(bench.cell(
                            args.workload)["chips"])), args.seed)
    windows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        w, before, after, compiles = cell.measure(mix, args.seconds, None)
        windows.append(w)
        lat = [r.done - r.due for r in w.requests]        # in due order
        q = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate_per_s": rate, "attempted": w.attempted,
            "failed": w.failed,
            "completed_per_s": sum(r.ok for r in w.requests) / w.close,
            "p50_ms": 1e3 * traffic.percentile(lat, 50),
            "p95_ms": 1e3 * traffic.percentile(lat, 95),
            "backlog_growth": float(np.median(lat[-q:]) /
                                    np.median(lat[:q])),
            "batch_width": batch_width(types.SimpleNamespace(
                stats_before=before, stats_after=after)),
            "compiles_in_window": compiles}), flush=True)
    cell.release()
    print(json.dumps({"checks": cell.check(windows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
