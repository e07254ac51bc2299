#!/usr/bin/env python3
"""Read the control of a cell's comparison on the chip.

    python3 chip_bench/control.py --workload cop20k_A_synth.closed8 --seeds 101,102,103

For each seed each tenant's matrix and request vectors are made as a run
makes them, and the control (``reference.bf16_control``: values and x
rounded to bfloat16, products summed in float32) answers the requests in
the order a run sends them, at most ``--answers`` of them (default: the
whole pool).  Each answer is judged by the comparison that decides ``correct``; one line
per seed gives the largest ``max_norm_err`` beside the configuration's
limit, which the control has to exceed.  The benchmark's runs never run
this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_readings(bench, name: str, seeds, answers: int | None, device):
    """[(seed, control's max_norm_err over the answers)]."""
    from chip_bench import reference, traffic
    from chip_bench.matrix import build_matrix

    cell = bench.cell(name)
    config, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    out = []
    for seed in seeds:
        worst = 0.0
        for t, spec in enumerate(config["tenants"]):
            A = build_matrix(spec, seed, t)
            pool = traffic.request_pool(A.ncols, int(mix["batch"]),
                                        int(mix["pool"]), seed, t)
            order = traffic.pool_order(len(pool), seed)[:answers]
            ys = reference.bf16_control(A, pool[order], device)
            ref = reference.Reference(A)
            worst = max([worst] + [ref.norm_err(y, *ref.answer(pool[i]))
                                   for i, y in zip(order, ys)])
        out.append((seed, worst))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--answers", type=int, default=None)
    args = ap.parse_args(argv)

    from chip_bench import cells, run

    bench = cells.Benchmark.load(ROOT)
    device = run.require_chips(1)[0]
    limit = bench.config(bench.cell(args.workload)["config"])[
        "limits"]["max_norm_err"]
    for seed, worst in control_readings(
            bench, args.workload, [int(s) for s in args.seeds.split(",")],
            args.answers, device):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_max_norm_err": worst, "limit": limit,
                          "fails": worst > limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
