"""Sparsity-pattern generators, one module per ``pattern`` that a
configuration names.  Each module has ``SYMMETRIC`` (whether the pattern is
mirrored) and ``coo(M, nnz, *, seed, **params) -> (rows, cols)``, the entries
before mirroring, duplicates and out-of-range ones included.
"""
