"""Kernel micro-bench: Pallas-oracle parity cost on CPU (interpret mode is
a correctness vehicle; real perf numbers come from the TPU dry-run).
Reports us/call of the jnp oracle paths that the models actually execute."""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.program import kernel_interpret
from repro.core.sparse_matrix import csr_from_coo, csr_to_ell
from repro.data.matrices import blocked_band, powerlaw, powerlaw_tail
from repro.kernels import ops
from .common import emit, us


def run():
    # Pallas kernels run interpreted on the CPU and compiled on the chip.
    interp = kernel_interpret(jax.default_backend())
    rng = np.random.default_rng(0)
    rows = []
    for M, N, nnz in ((512, 512, 8000), (2048, 2048, 40000)):
        A = csr_from_coo(rng.integers(0, M, nnz), rng.integers(0, N, nnz),
                         rng.standard_normal(nnz), (M, N))
        x = jnp.asarray(rng.standard_normal(N), jnp.float32)
        e = csr_to_ell(A)
        data, cols = jnp.asarray(e.data), jnp.asarray(e.cols)
        t = us(lambda: ops.ell_spmv_ref(data, cols, x).block_until_ready())
        rows.append((f"ell_ref/{M}x{N}/nnz{nnz}", round(t, 1),
                     f"pad={e.padding_ratio:.2f}"))
        tm = ops.tile_from_csr(A)
        t = us(lambda: ops.tile_spmv(tm, x).block_until_ready())
        rows.append((f"tile_ref/{M}x{N}/nnz{nnz}", round(t, 1),
                     f"tiles={tm.num_tiles};fill={tm.fill_ratio:.2f}"))
        # Segmented (nonzero-balanced) family: oracle path timing on the
        # uniform matrix above plus a skewed power-law one, where the
        # row-tiled ELL slab pays max-row-nnz padding and the seg slab
        # stays at ~chunk granularity (see the pad/chunks column).
        seg = ops.seg_from_csr(A)
        t = us(lambda: ops.seg_spmv(seg, x).block_until_ready())
        rows.append((f"seg_ref/{M}x{N}/nnz{nnz}", round(t, 1),
                     f"chunks={seg.num_chunks};pieces={seg.n_pieces};"
                     f"pad={seg.padding_ratio:.2f}"))
    P = powerlaw(2048, 40_000, seed=0)
    xp = jnp.asarray(rng.standard_normal(P.ncols), jnp.float32)
    e = csr_to_ell(P)
    data, cols = jnp.asarray(e.data), jnp.asarray(e.cols)
    t = us(lambda: ops.ell_spmv_ref(data, cols, xp).block_until_ready())
    rows.append((f"ell_ref/powerlaw2048/nnz{P.nnz}", round(t, 1),
                 f"pad={e.padding_ratio:.2f}"))
    seg = ops.seg_from_csr(P)
    t = us(lambda: ops.seg_spmv(seg, xp).block_until_ready())
    rows.append((f"seg_ref/powerlaw2048/nnz{P.nnz}", round(t, 1),
                 f"chunks={seg.num_chunks};pieces={seg.n_pieces};"
                 f"pad={seg.padding_ratio:.2f}"))
    # Split-nnz (two-stage) family: the seg slab with each row's carry
    # chain cut across num_splits partial accumulators.  Timed on the
    # same power-law matrix and on a monster-row matrix (a handful of
    # fully dense rows — the §IV-D hot spot the family exists for),
    # oracle path and Pallas-interpret kernel path.
    for name, Q in (("powerlaw2048", P),
                    ("monster2048", powerlaw_tail(2048, 2 * 4 * 2048,
                                                  n_monster=4, seed=0))):
        xq = jnp.asarray(rng.standard_normal(Q.ncols), jnp.float32)
        for ns in (2, 8):
            spl = ops.split_from_csr(Q, ns)
            t = us(lambda: ops.split_spmv(spl, xq).block_until_ready())
            rows.append((f"split_ref/{name}/nnz{Q.nnz}/ns{spl.num_splits}",
                         round(t, 1),
                         f"chunks={spl.chunks_per_split};"
                         f"pieces={spl.n_pieces};"
                         f"pad={spl.padding_ratio:.2f}"))
        spl = ops.split_from_csr(Q, 8)
        t = us(lambda: ops.split_spmv(spl, xq, use_kernel=True,
                                      interpret=interp).block_until_ready())
        rows.append((f"split_pallas/{name}/nnz{Q.nnz}/ns{spl.num_splits}",
                     round(t, 1), f"interpret={interp}"))
    # Bitmask-tiled family: its win case is block-structured data (dense
    # (8, 128) tiles, fill -> 1.0); the scattered powerlaw row above it
    # shows the loss case (fill -> 0, every tile mostly padding).  Oracle
    # path on both, Pallas scalar-prefetch walk (interpret) on the win.
    B = blocked_band(2048, 215 * 2048, seed=0)
    xb = jnp.asarray(rng.standard_normal(B.ncols), jnp.float32)
    for name, Q, xq in (("blocked2048", B, xb), ("powerlaw2048", P, xp)):
        tm = ops.tile_from_csr(Q)
        t = us(lambda: ops.tile_spmv(tm, xq).block_until_ready())
        rows.append((f"tile_ref/{name}/nnz{Q.nnz}", round(t, 1),
                     f"tiles={tm.num_tiles};fill={tm.fill_ratio:.2f}"))
    tm = ops.tile_from_csr(B)
    t = us(lambda: ops.tile_spmv(tm, xb, use_kernel=True,
                                 interpret=interp).block_until_ready())
    rows.append((f"tile_pallas/blocked2048/nnz{B.nnz}", round(t, 1),
                 f"interpret={interp}"))
    emit(rows, ("name", "us_per_call", "derived"))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
