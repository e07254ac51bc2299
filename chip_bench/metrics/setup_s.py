"""Seconds from process start to the end of warm-up: generating the matrix
and the request vectors, the cold ingest, and one call of every program
shape the mix uses (compiling, or loading from the persistent cache)."""


def read(run):
    return run.setup_s
