#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chip_bench/run.py --workload cop20k_A_synth.closed8 --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last the
``checks``: each number compared with its limit, also printed as the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"chip_bench: {msg}", file=sys.stderr)
    return 1


def require_chips(n: int):
    """The first ``n`` TPU devices; raises RuntimeError without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {devices[0].platform} "
                           "devices only")
    if len(devices) < n:
        raise RuntimeError(f"the cell needs {n} TPUs, JAX found "
                           f"{len(devices)}")
    return devices[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="copy the raw profiler trace into this directory")
    args = ap.parse_args(argv)

    try:
        from chip_bench import cells, harness
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as err:
        return fail(f"the benchmark or the program is missing ({err})")
    bench = cells.Benchmark.load(ROOT)
    try:
        cell = bench.cell(args.workload)
    except KeyError as err:
        return fail(str(err))
    try:
        devices = require_chips(int(cell["chips"]))
    except RuntimeError as err:
        return fail(str(err))
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run_cell(bench, args.workload, devices, args.seed,
                              args.seconds, bool(args.trace), T_PROCESS,
                              keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # The benchmark's package and the program, in place of this script's
    # own directory, whose module names would shadow others.
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
