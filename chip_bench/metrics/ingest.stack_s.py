"""Seconds of the cold ingest's ``ingest.stack`` span, summed over the
cell's tenants, from the engine's ``stats()[tenant]["ingest_phases_s"]``:
building the device operand sets (every shard's local and remote slices, stacked)."""
from chip_bench.program_spans import ingest_phase_s


def read(run):
    return ingest_phase_s(run, "stack")
