"""Seconds of the cold ingest's ``ingest.place`` span, summed over the
cell's tenants, from the engine's ``stats()[tenant]["ingest_phases_s"]``:
the `device_put` of those operands onto the mesh, as far as ingest waits for it."""
from chip_bench.program_spans import ingest_phase_s


def read(run):
    return ingest_phase_s(run, "place")
