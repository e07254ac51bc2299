"""Seconds of the cold ingest's ``ingest.plan`` span, summed over the
cell's tenants, from the engine's ``stats()[tenant]["ingest_phases_s"]``:
the features, then the autotune, the plan-cache lookup or the given plan's cost (a warm start's load of its artifact too)."""
from chip_bench.program_spans import ingest_phase_s


def read(run):
    return ingest_phase_s(run, "plan")
