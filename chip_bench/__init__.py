"""The chip benchmark of the SpMV serving engine.

One command runs one cell (a configuration under a traffic mix) once:

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the repository; each lives in a file of
its own under this directory, found by that name.  Everything that turns a
run into numbers (traffic generation, the float64 reference, the trace
reduction, the table of peaks and the byte floor) is kept here, apart from
the program under test.
"""
