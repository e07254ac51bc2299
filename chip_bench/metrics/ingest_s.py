"""Wall seconds of the cold ``SparseMatrixEngine.ingest`` of the cell's
tenant (autotune or the given plan, lowering, placing the operands), with
no artifact store and a fresh plan cache: what a user waits to register a
matrix."""


def read(run):
    return run.ingest_s
