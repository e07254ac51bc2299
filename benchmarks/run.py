"""Benchmark entry point: one section per paper table/figure.

Prints ``name,...`` CSV blocks.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    import json

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (autotune_bench, bottleneck_bench, fig3_layout,
                   fig6_distribution, fig7_cv, fig8_residency, fig10_reorder,
                   fig12_cache, hetero_bench)
    sections = [
        ("Fig.3 cyclic-vs-block", fig3_layout.run),
        # fast=True keeps the all-sections sweep snappy; run the fig6/fig8
        # modules standalone for the full synthetic matrix sizes.
        ("Fig.6 row-vs-nonzero", lambda: fig6_distribution.run(fast=True)),
        ("Fig.7 mem-instr CV", fig7_cv.run),
        ("Fig.8/11 residency", lambda: fig8_residency.run(fast=True)),
        ("Fig.10 reorderings (Emu)", fig10_reorder.run),
        ("Fig.12 reorderings (cache CPU)", fig12_cache.run),
        ("Autotuner chosen-vs-best-static", autotune_bench.run),
        ("Per-shard program vs best global (hetero)",
         lambda: print(json.dumps(hetero_bench.run_hetero_bench(fast=True),
                                  indent=2))),
        ("Bottleneck oracle: gated vs always-re-plan",
         lambda: print(json.dumps(
             bottleneck_bench.run_bottleneck_bench(scale=0.003, window=16),
             indent=2))),
    ]
    failures = 0
    for title, fn in sections:
        print(f"# === {title} ===")
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
