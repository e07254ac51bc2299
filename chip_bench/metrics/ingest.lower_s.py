"""Seconds of the cold ingest's ``ingest.lower`` span, summed over the
cell's tenants, from the engine's ``stats()[tenant]["ingest_phases_s"]``:
lowering the plan to the per-shard program."""
from chip_bench.program_spans import ingest_phase_s


def read(run):
    return ingest_phase_s(run, "lower")
