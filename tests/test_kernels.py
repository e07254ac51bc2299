"""Per-kernel tests: Pallas (interpret=True) vs pure-jnp oracle vs dense.

Every kernel pads its operands to the (8, 128) tile and picks aligned
block extents itself (``kernels/tiling.py``), so the odd shapes below run
the same block shapes the chip compiles; ``tests/test_tpu_compile.py``
compiles them for a described TPU v5e.

Shape/dtype sweeps + hypothesis property tests, per the assignment brief.
``hypothesis`` is an optional extra: without it only the property-test
class is skipped — the sweep tests always collect and run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

    def given(*_a, **_k):            # no-op stand-ins so the decorated
        return lambda f: f           # (skipped) class still defines

    def settings(*_a, **_k):
        return lambda f: f

    class _AnyStrategy:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _AnyStrategy()

from repro.core.sparse_matrix import csr_from_coo, csr_matvec, csr_to_bcsr, \
    csr_to_dense, csr_to_ell
from repro.data.matrices import powerlaw, powerlaw_tail
from repro.kernels import ops, ref


def _np_slab_oracle(vals, cols, rows, x, num_rows):
    """Float64 numpy ground truth for any seg/split-style (..., L) slab:
    scatter-add every slot into its output row.  Padded slots carry
    ``val == 0`` so they contribute exactly nothing."""
    y = np.zeros(num_rows, np.float64)
    np.add.at(y, np.asarray(rows).reshape(-1),
              (np.asarray(vals, np.float64) *
               np.asarray(x, np.float64)[np.asarray(cols)]).reshape(-1))
    return y


def rand_problem(M, N, nnz, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    A = csr_from_coo(rng.integers(0, M, nnz), rng.integers(0, N, nnz),
                     rng.standard_normal(nnz), (M, N))
    x = rng.standard_normal(N).astype(dtype)
    return A, x


class TestEllKernel:
    @pytest.mark.parametrize("M,N,nnz", [(8, 128, 50), (64, 256, 900),
                                         (256, 512, 5000)])
    @pytest.mark.parametrize("dtype", [jnp.float32])
    def test_matches_oracle_and_dense(self, M, N, nnz, dtype):
        A, x = rand_problem(M, N, nnz)
        e = csr_to_ell(A)
        data, cols = jnp.asarray(e.data, dtype), jnp.asarray(e.cols)
        xj = jnp.asarray(x, dtype)
        y_ref = ref.ell_spmv_ref(data, cols, xj)
        y_pal = ops.ell_spmv(data, cols, xj, interpret=True,
                             tile_m=8, tile_w=128)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y_pal)[:M],
                                   csr_to_dense(A) @ x, rtol=1e-3, atol=1e-3)

    def test_tile_sweep(self):
        A, x = rand_problem(64, 256, 1500, seed=3)
        e = csr_to_ell(A)
        data, cols, xj = map(jnp.asarray, (e.data, e.cols, x))
        base = None
        for tm in (8, 16, 32, 64):
            for tw in (128, e.data.shape[1]):
                y = np.asarray(ops.ell_spmv(data, cols, xj, interpret=True,
                                            tile_m=tm, tile_w=tw))
                if base is None:
                    base = y
                np.testing.assert_allclose(y, base, rtol=1e-5)

    def test_hyb_overflow_path(self):
        A, x = rand_problem(128, 128, 4000, seed=5)
        e = csr_to_ell(A, lane=8, max_width=8)
        assert e.overflow_vals.size > 0
        y = ops.hyb_spmv(*map(jnp.asarray, (e.data, e.cols, e.overflow_rows,
                                            e.overflow_cols, e.overflow_vals,
                                            x)))
        np.testing.assert_allclose(np.asarray(y)[:128], csr_to_dense(A) @ x,
                                   rtol=1e-3, atol=1e-3)

    def test_batched_matches_per_vector(self):
        """Multi-RHS (N, B): every column equals its per-vector run, for
        the oracle and for the (vmapped) Pallas kernel path."""
        A, _ = rand_problem(64, 256, 900, seed=7)
        e = csr_to_ell(A)
        data, cols = jnp.asarray(e.data), jnp.asarray(e.cols)
        X = np.random.default_rng(7).standard_normal((256, 3)) \
            .astype(np.float32)
        Y_ref = np.asarray(ref.ell_spmv_ref(data, cols, jnp.asarray(X)))
        Y_pal = np.asarray(ops.ell_spmv(data, cols, jnp.asarray(X),
                                        interpret=True, tile_m=8,
                                        tile_w=128))
        assert Y_ref.shape == (e.data.shape[0], 3)
        for b in range(3):
            # fp32 XLA reductions may re-associate across batch widths, so
            # the jnp paths are compared at tight tolerance (the *numpy*
            # serving path, local_spmv, is the bitwise-exact one — see
            # tests/test_serve_engine.py).
            np.testing.assert_allclose(
                Y_ref[:, b],
                np.asarray(ref.ell_spmv_ref(data, cols,
                                            jnp.asarray(X[:, b]))),
                rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(
                Y_pal[:, b],
                np.asarray(ops.ell_spmv(data, cols, jnp.asarray(X[:, b]),
                                        interpret=True, tile_m=8,
                                        tile_w=128)),
                rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(Y_ref[:64], csr_to_dense(A) @ X,
                                   rtol=1e-3, atol=1e-3)


class TestTileKernel:
    """Bitmask-tiled SpMV: pointer-grid walk (oracle + Pallas interpret)
    vs dense, the occupancy bitmask, the flat device path, and the
    deprecated Block-ELL shims that now route through it."""

    @pytest.mark.parametrize("bm,bn", [(8, 128), (16, 128)])
    def test_spmv_matches(self, bm, bn):
        A, x = rand_problem(256, 256, 3000, seed=1)
        t = ops.tile_from_csr(A, bm=bm, bn=bn)
        xj = jnp.asarray(x)
        y_ref = ops.tile_spmv(t, xj)
        y_pal = ops.tile_spmv(t, xj, use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y_pal)[:256],
                                   csr_to_dense(A) @ x, rtol=1e-3, atol=1e-3)

    def test_batched_matches_per_vector(self):
        A, _ = rand_problem(256, 256, 2000, seed=2)
        X = np.random.default_rng(7).standard_normal((256, 3)) \
            .astype(np.float32)
        t = ops.tile_from_csr(A)
        Y_ref = np.asarray(ops.tile_spmv(t, jnp.asarray(X)))
        Y_pal = np.asarray(ops.tile_spmv(t, jnp.asarray(X),
                                         use_kernel=True, interpret=True))
        assert Y_ref.shape == (256, 3)
        for b in range(3):
            np.testing.assert_allclose(
                Y_ref[:, b],
                np.asarray(ops.tile_spmv(t, jnp.asarray(X[:, b]))),
                rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(Y_pal, Y_ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(Y_ref[:256], csr_to_dense(A) @ X,
                                   rtol=1e-3, atol=1e-3)

    def test_bitmask_counts_stored_entries_and_ptr_grid_is_sorted(self):
        """The packed occupancy mask records *stored* entries (stored
        zeros included, structural zeros excluded), and the coarse
        pointer level walks tiles block-row-major, sorted by block col."""
        rng = np.random.default_rng(3)
        n = 1500
        rows, cols = rng.integers(0, 256, n), rng.integers(0, 256, n)
        vals = rng.standard_normal(n)
        vals[:10] = 0.0                       # explicit stored zeros
        A = csr_from_coo(rows, cols, vals, (256, 256))
        t = ops.tile_from_csr(A)
        occ = t.occupancy()
        assert int(occ.sum()) == A.nnz == t.nnz
        # stored zeros occupy cells the dense payload cannot distinguish
        assert int((t.data != 0).sum()) < t.nnz
        assert t.tile_ptr[0] == 0 and t.tile_ptr[-1] == t.num_tiles
        for mb in range(t.tile_ptr.size - 1):
            lo, hi = int(t.tile_ptr[mb]), int(t.tile_ptr[mb + 1])
            assert (t.tile_rows[lo:hi] == mb).all()
            assert (np.diff(t.tile_cols[lo:hi]) > 0).all()

    def test_flat_path_matches_structured(self):
        """``tile_flat_spmv`` (pre-gathered per-lane x positions + block
        rows, the device-path operands) agrees with the structured walk,
        padding tiles dropping past the last block row."""
        A, x = rand_problem(256, 256, 3000, seed=4)
        t = ops.tile_from_csr(A)
        Tn, Rb = t.num_tiles, -(-256 // t.bm)
        Tp = Tn + 3                           # padding tiles must drop
        data = np.zeros((Tp, t.bm, t.bn), np.float32)
        data[:Tn] = t.data
        xcols = np.zeros((Tp, t.bn), np.int32)
        xcols[:Tn] = np.minimum(
            t.tile_cols[:, None] * t.bn + np.arange(t.bn)[None, :], 255)
        trows = np.full(Tp, Rb, np.int32)
        trows[:Tn] = t.tile_rows
        for use_kernel in (False, True):
            y = np.asarray(ops.tile_flat_spmv(
                jnp.asarray(data), jnp.asarray(xcols), jnp.asarray(trows),
                jnp.asarray(x), num_rows=256, use_kernel=use_kernel,
                interpret=use_kernel))
            np.testing.assert_allclose(
                y, np.asarray(ops.tile_spmv(t, jnp.asarray(x))),
                rtol=1e-5, atol=1e-5)

    def test_empty_matrix_is_noop(self):
        E = csr_from_coo(np.zeros(0, int), np.zeros(0, int), np.zeros(0),
                         (16, 16))
        t = ops.tile_from_csr(E)
        assert t.num_tiles == 0
        y = np.asarray(ops.tile_spmv(t, jnp.zeros(16, jnp.float32)))
        assert y.shape == (16,) and not y.any()

    @pytest.mark.parametrize("B,tb", [(128, 128), (256, 128)])
    def test_deprecated_bell_shims_warn_once_and_match(self, B, tb):
        """The retired Block-ELL API stays importable: ``bell_*`` warn
        (once per process) and route through the tile walk, matching the
        kept ``ref.bell_*_ref`` oracles and dense."""
        from repro.core.spmv import _DEPRECATION_WARNED
        A, x = rand_problem(256, 256, 2000, seed=2)
        _DEPRECATION_WARNED.discard("bell_from_bcsr")
        with pytest.warns(DeprecationWarning, match="tile_from_csr"):
            blocks, bcols = ops.bell_from_bcsr(csr_to_bcsr(A, (8, 128)))
        bj, cj = jnp.asarray(blocks), jnp.asarray(bcols)
        _DEPRECATION_WARNED.discard("bell_spmv")
        with pytest.warns(DeprecationWarning, match="tile_spmv"):
            y = ops.bell_spmv(bj, cj, jnp.asarray(x), use_kernel=True,
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.bell_spmv_ref(bj, cj,
                                                        jnp.asarray(x))),
            rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y)[:256], csr_to_dense(A) @ x,
                                   rtol=1e-3, atol=1e-3)
        X = np.random.default_rng(7).standard_normal((256, B)) \
            .astype(np.float32)
        _DEPRECATION_WARNED.discard("bell_spmm")
        with pytest.warns(DeprecationWarning, match="tile_spmv"):
            Y = ops.bell_spmm(bj, cj, jnp.asarray(X), use_kernel=True,
                              interpret=True, tile_b=tb)
        np.testing.assert_allclose(np.asarray(Y)[:256], csr_to_dense(A) @ X,
                                   rtol=1e-3, atol=1e-3)


class TestSegKernel:
    """Nonzero-balanced segmented SpMV: kernel vs oracle vs dense."""

    @pytest.mark.parametrize("M,nnz", [(512, 4000), (2048, 16000)])
    def test_matches_oracle_and_dense_on_powerlaw(self, M, nnz):
        """Skewed power-law matrix (max-row-nnz >> mean): the load-balance
        case the row-tiled ELL kernel handles worst."""
        A = powerlaw(M, nnz, seed=3)
        row_nnz = np.diff(A.row_ptr)
        assert row_nnz.max() > 5 * row_nnz.mean()       # genuinely skewed
        x = jnp.asarray(np.random.default_rng(0).standard_normal(M),
                        jnp.float32)
        seg = ops.seg_from_csr(A)
        y_ref = np.asarray(ops.seg_spmv(seg, x))
        y_pal = np.asarray(ops.seg_spmv(seg, x, use_kernel=True,
                                        interpret=True))
        np.testing.assert_allclose(y_pal, y_ref, rtol=1e-5, atol=1e-5)
        dense = csr_to_dense(A) @ np.asarray(x)
        np.testing.assert_allclose(y_ref, dense, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(y_pal, dense, rtol=1e-4, atol=1e-5)

    def test_row_spanning_many_chunks(self):
        """One dense row (nnz >> chunk) must sum one carry per chunk."""
        rng = np.random.default_rng(1)
        M = 512
        r = np.concatenate([np.zeros(5000, int), rng.integers(1, M, 1000)])
        c = rng.integers(0, M, 6000)
        A = csr_from_coo(r, c, rng.standard_normal(6000), (M, M))
        x = jnp.asarray(rng.standard_normal(M), jnp.float32)
        seg = ops.seg_from_csr(A, chunk=128)
        assert np.diff(A.row_ptr)[0] > 3 * seg.chunk    # spans >= 4 chunks
        y = np.asarray(ops.seg_spmv(seg, x, use_kernel=True, interpret=True))
        np.testing.assert_allclose(y, csr_to_dense(A) @ np.asarray(x),
                                   rtol=1e-4, atol=1e-5)

    def test_chunk_and_tile_sweep(self):
        A = powerlaw(1024, 8000, seed=5)
        x = jnp.asarray(np.random.default_rng(2).standard_normal(1024),
                        jnp.float32)
        base = None
        for chunk in (128, 256, 512):
            seg = ops.seg_from_csr(A, chunk=chunk)
            for tc in (1, 2, 8):
                if seg.num_chunks % tc:
                    continue
                y = np.asarray(ops.seg_spmv(seg, x, use_kernel=True,
                                            interpret=True, tile_c=tc))
                if base is None:
                    base = y
                np.testing.assert_allclose(y, base, rtol=1e-5, atol=1e-5)

    def test_empty_rows_and_empty_matrix(self):
        A = csr_from_coo([1, 1, 5], [0, 3, 2], [1.0, 2.0, 3.0], (8, 8))
        x = jnp.asarray(np.arange(8, dtype=np.float32))
        seg = ops.seg_from_csr(A)
        y = np.asarray(ops.seg_spmv(seg, x, use_kernel=True, interpret=True))
        np.testing.assert_allclose(y, csr_to_dense(A) @ np.asarray(x),
                                   atol=1e-6)
        E = csr_from_coo(np.zeros(0, int), np.zeros(0, int), np.zeros(0),
                         (16, 16))
        se = ops.seg_from_csr(E)
        ye = np.asarray(ops.seg_spmv(se, jnp.zeros(16, jnp.float32),
                                     use_kernel=True, interpret=True))
        assert ye.shape == (16,) and not ye.any()

    def test_batched_matches_per_vector(self):
        """Multi-RHS (N, B) through the seg oracle and the vmapped kernel
        path: every column equals its per-vector run."""
        A = powerlaw(512, 4000, seed=9)
        X = np.random.default_rng(9).standard_normal((512, 3)) \
            .astype(np.float32)
        seg = ops.seg_from_csr(A)
        Y_ref = np.asarray(ops.seg_spmv(seg, jnp.asarray(X)))
        Y_pal = np.asarray(ops.seg_spmv(seg, jnp.asarray(X),
                                        use_kernel=True, interpret=True))
        assert Y_ref.shape == (512, 3)
        for b in range(3):
            np.testing.assert_allclose(
                Y_ref[:, b],
                np.asarray(ops.seg_spmv(seg, jnp.asarray(X[:, b]))),
                rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(
                Y_pal[:, b],
                np.asarray(ops.seg_spmv(seg, jnp.asarray(X[:, b]),
                                        use_kernel=True, interpret=True)),
                rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(Y_ref, csr_to_dense(A) @ X,
                                   rtol=1e-4, atol=1e-4)

    def test_monster_row_carry_pinned_vs_csr_matvec(self):
        """Regression pin for the seg carry fix-up when a *single* row
        spans many chunks: one fully dense row (span 16 under chunk=128)
        over a thin background must reproduce ``csr_matvec`` through the
        oracle and the Pallas path, and a float64 scatter over the slab
        must match ``csr_matvec`` on the same (fp32-stored) values to
        fp64 round-off — the carry chain either sums every chunk's carry
        exactly once or drifts visibly."""
        rng = np.random.default_rng(11)
        M = 2048
        r = np.concatenate([np.zeros(M, int), np.arange(1, M)])
        c = np.concatenate([np.arange(M), rng.integers(0, M, M - 1)])
        v = rng.standard_normal(2 * M - 1)
        A = csr_from_coo(r, c, v, (M, M))
        seg = ops.seg_from_csr(A, chunk=128)
        assert np.diff(A.row_ptr)[0] == M          # monster row intact
        assert M // seg.chunk >= 16                # spans >= 16 chunks
        x = rng.standard_normal(M)
        want = csr_matvec(A, x)
        xj = jnp.asarray(x, jnp.float32)
        y_ref = np.asarray(ops.seg_spmv(seg, xj))
        y_pal = np.asarray(ops.seg_spmv(seg, xj, use_kernel=True,
                                        interpret=True))
        # fp32 paths: the monster row sums 2048 terms — scale tolerance
        np.testing.assert_allclose(y_ref, want, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(y_pal, want, rtol=1e-4, atol=1e-3)
        A32 = dataclasses.replace(
            A, values=A.values.astype(np.float32).astype(np.float64))
        y64 = _np_slab_oracle(seg.vals, seg.cols, seg.rows, x, M)
        np.testing.assert_allclose(y64, csr_matvec(A32, x),
                                   rtol=1e-12, atol=1e-12)

    def test_grid_is_nnz_balanced(self):
        """Structural invariant: every chunk except the last holds exactly
        ``chunk`` non-zeros, no matter how skewed the rows are — the whole
        point of the format."""
        A = powerlaw(1024, 12000, seed=7)
        seg = ops.seg_from_csr(A, chunk=256)
        per_chunk = np.zeros(seg.num_chunks, np.int64)
        flat_c = np.arange(A.nnz) // seg.chunk
        np.add.at(per_chunk, flat_c, 1)
        full = per_chunk[per_chunk > 0]
        assert (full[:-1] == seg.chunk).all() and full[-1] <= seg.chunk
        # pieces tile the stream exactly once
        assert seg.piece_row.size >= A.shape[0] - (np.diff(A.row_ptr) == 0).sum()
        covered = 0
        for ch, lo, hi in zip(seg.piece_chunk, seg.piece_lo, seg.piece_hi):
            assert 0 <= lo <= hi < seg.chunk
            covered += hi - lo + 1
        assert covered == A.nnz


class TestSplitKernel:
    """Split-nnz two-stage SpMV: stage-1 per-split prefix sums + carry
    fix-up into (NS, rows) partials, stage-2 segmented combine."""

    @pytest.mark.parametrize("ns", [1, 2, 3, 4, 8])
    def test_matches_seg_and_float64_oracle(self, ns):
        A = powerlaw(1024, 12000, seed=4)
        x = np.random.default_rng(4).standard_normal(1024)
        xj = jnp.asarray(x, jnp.float32)
        spl = ops.split_from_csr(A, ns)
        seg = ops.seg_from_csr(A)
        y_spl = np.asarray(ops.split_spmv(spl, xj))
        y_seg = np.asarray(ops.seg_spmv(seg, xj))
        np.testing.assert_allclose(y_spl, y_seg, rtol=1e-5, atol=1e-5)
        y64 = _np_slab_oracle(spl.vals, spl.cols, spl.rows, x, 1024)
        np.testing.assert_allclose(y_spl, y64, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("ns", [2, 4])
    def test_pallas_two_stage_matches_oracle(self, ns):
        """stage-1 ``split_psum`` + fix-up + stage-2 ``split_combine``
        (interpret mode) vs the jnp oracle, on a monster-row matrix."""
        A = powerlaw_tail(1024, 2 * 4 * 1024, n_monster=4, seed=2)
        x = jnp.asarray(np.random.default_rng(2).standard_normal(1024),
                        jnp.float32)
        spl = ops.split_from_csr(A, ns)
        y_ref = np.asarray(ops.split_spmv(spl, x))
        y_pal = np.asarray(ops.split_spmv(spl, x, use_kernel=True,
                                          interpret=True))
        np.testing.assert_allclose(y_pal, y_ref, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(
            y_pal, csr_matvec(A, np.asarray(x, np.float64)),
            rtol=1e-3, atol=1e-2)

    def test_monster_row_split_kills_carry_span(self):
        """The structural point of the format: a row spanning ``span``
        chunks in seg spans at most ``ceil(C/ns)`` chunks of each split's
        slab (the splits cut the flat chunk stream, so the boundaries
        land inside the row once ``ns > C/span``), and the result is
        unchanged."""
        A = powerlaw_tail(512, 2 * 2 * 512, n_monster=2, seed=0)
        seg = ops.seg_from_csr(A, chunk=128)      # monster rows span 4+
        spl = ops.split_from_csr(A, 10, chunk=128)   # 2-chunk splits
        span_seg = max(np.bincount(seg.piece_row,
                                   minlength=A.shape[0]).max(), 1)
        span_spl = 0
        for s in range(spl.num_splits):
            m = spl.piece_split == s
            if m.any():
                span_spl = max(span_spl, np.bincount(
                    spl.piece_row[m], minlength=A.shape[0]).max())
        assert span_spl < span_seg
        x = jnp.asarray(np.random.default_rng(1).standard_normal(512),
                        jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ops.split_spmv(spl, x)),
            np.asarray(ops.seg_spmv(seg, x)), rtol=1e-5, atol=1e-5)

    def test_batched_matches_per_vector(self):
        """(N, B) batched split SpMV: every column equals its per-vector
        run — exactly for the oracle path, tightly for the vmapped
        Pallas path."""
        A = powerlaw_tail(512, 2 * 2 * 512, n_monster=2, seed=5)
        X = np.random.default_rng(5).standard_normal((512, 3)) \
            .astype(np.float32)
        spl = ops.split_from_csr(A, 4)
        Y_ref = np.asarray(ops.split_spmv(spl, jnp.asarray(X)))
        Y_pal = np.asarray(ops.split_spmv(spl, jnp.asarray(X),
                                          use_kernel=True, interpret=True))
        assert Y_ref.shape == (512, 3)
        for b in range(3):
            np.testing.assert_allclose(
                Y_ref[:, b],
                np.asarray(ops.split_spmv(spl, jnp.asarray(X[:, b]))),
                rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(
                Y_pal[:, b],
                np.asarray(ops.split_spmv(spl, jnp.asarray(X[:, b]),
                                          use_kernel=True, interpret=True)),
                rtol=1e-5, atol=1e-5)

    def test_empty_matrix_and_count_clamp(self):
        """Zero-nnz matrices lower to a valid no-op split slab for every
        requested count, and absurd counts clamp to the chunk count."""
        E = csr_from_coo(np.zeros(0, int), np.zeros(0, int), np.zeros(0),
                         (16, 16))
        for ns in (1, 4, 999):
            spl = ops.split_from_csr(E, ns)
            assert spl.num_splits == 1            # clamped to C == 1
            y = np.asarray(ops.split_spmv(spl, jnp.zeros(16, jnp.float32),
                                          use_kernel=True, interpret=True))
            assert y.shape == (16,) and not y.any()
        A = powerlaw(256, 2000, seed=6)
        spl = ops.split_from_csr(A, 10**6)
        assert 1 <= spl.num_splits <= spl.chunks_per_split * spl.num_splits
        x = jnp.asarray(np.random.default_rng(6).standard_normal(256),
                        jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ops.split_spmv(spl, x)),
            np.asarray(ops.seg_spmv(ops.seg_from_csr(A), x)),
            rtol=1e-5, atol=1e-5)

    def test_flat_path_matches_structured(self):
        """``split_flat_spmv`` (the device-path flattened slab + widened
        piece table) agrees with the structured ``split_spmv``."""
        A = powerlaw_tail(512, 2 * 2 * 512, n_monster=2, seed=8)
        x = jnp.asarray(np.random.default_rng(8).standard_normal(512),
                        jnp.float32)
        spl = ops.split_from_csr(A, 4)
        ns, Cs = spl.num_splits, spl.chunks_per_split
        pieces = np.stack([spl.piece_split * Cs + spl.piece_chunk,
                           spl.piece_lo, spl.piece_hi, spl.piece_row,
                           spl.piece_split], axis=1).astype(np.int32)
        L = spl.vals.shape[-1]
        y_flat = np.asarray(ops.split_flat_spmv(
            jnp.asarray(spl.vals.reshape(ns * Cs, L)),
            jnp.asarray(spl.cols.reshape(ns * Cs, L)),
            jnp.asarray(spl.rows.reshape(ns * Cs, L)),
            jnp.asarray(pieces), x, num_rows=512, num_splits=ns,
            use_kernel=True, interpret=True))
        np.testing.assert_allclose(y_flat, np.asarray(ops.split_spmv(spl, x)),
                                   rtol=1e-5, atol=1e-5)


class TestAlignedBlocks:
    """The padding and block-fitting paths that make each kernel's
    blocks (8, 128)-aligned, on shapes that are not."""

    @pytest.mark.parametrize("n,cap,align,want", [
        (120, 256, 8, 120), (120_000, 256, 8, 240), (512, 512, 128, 512),
        (640, 512, 128, 128), (8, 1, 8, 8), (1024, 100, 128, 128)])
    def test_fit_tile(self, n, cap, align, want):
        from repro.kernels.tiling import fit_tile
        t = fit_tile(n, cap, align)
        assert t == want and n % t == 0 and t % align == 0

    def test_fit_tile_rejects_unaligned_extent(self):
        from repro.kernels.tiling import fit_tile
        with pytest.raises(ValueError, match="multiple"):
            fit_tile(100, 64, 8)

    @pytest.mark.parametrize("L", [128, 512])
    def test_lane_scan_is_inclusive_prefix_sum(self, L):
        from jax.experimental import pallas as pl
        from repro.kernels.spmv_seg import lane_scan
        v = np.random.default_rng(L).standard_normal((16, L)) \
            .astype(np.float32)

        def k(v_ref, o_ref):
            o_ref[...] = lane_scan(v_ref[...])
        out = pl.pallas_call(k, out_shape=jax.ShapeDtypeStruct(v.shape,
                                                               v.dtype),
                             interpret=True)(jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(out), np.cumsum(v, axis=1),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("M,W", [(13, 40), (8, 128), (300, 200)])
    def test_ell_unaligned_slab(self, M, W):
        rng = np.random.default_rng(M)
        data = rng.standard_normal((M, W)).astype(np.float32)
        cols = rng.integers(0, 777, (M, W)).astype(np.int32)
        x = rng.standard_normal(777).astype(np.float32)
        y = np.asarray(ops.ell_spmv(jnp.asarray(data), jnp.asarray(cols),
                                    jnp.asarray(x), interpret=True))
        assert y.shape == (M,)
        np.testing.assert_allclose(y, (data * x[cols]).sum(axis=1),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("C", [1, 5, 13])
    def test_seg_psum_unaligned_chunk_count(self, C):
        from repro.kernels.spmv_seg import seg_psum
        rng = np.random.default_rng(C)
        vals = rng.standard_normal((C, 128)).astype(np.float32)
        cols = rng.integers(0, 300, (C, 128)).astype(np.int32)
        x = rng.standard_normal(300).astype(np.float32)
        ps = np.asarray(seg_psum(jnp.asarray(vals), jnp.asarray(cols),
                                 jnp.asarray(x), interpret=True))
        np.testing.assert_allclose(
            ps, np.asarray(ref.seg_psum_ref(vals, cols, x)),
            rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("NS,Cs", [(3, 5), (2, 16)])
    def test_split_psum_unaligned_split_slab(self, NS, Cs):
        from repro.kernels.spmv_split import split_psum
        rng = np.random.default_rng(NS * Cs)
        vals = rng.standard_normal((NS, Cs, 128)).astype(np.float32)
        cols = rng.integers(0, 200, (NS, Cs, 128)).astype(np.int32)
        x = rng.standard_normal(200).astype(np.float32)
        ps = np.asarray(split_psum(jnp.asarray(vals), jnp.asarray(cols),
                                   jnp.asarray(x), interpret=True))
        np.testing.assert_allclose(
            ps, np.asarray(ref.split_psum_ref(vals, cols, x)),
            rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("NS,R", [(1, 7), (5, 300), (64, 1024)])
    def test_split_combine_unaligned_rows(self, NS, R):
        from repro.kernels.spmv_split import split_combine
        part = np.random.default_rng(R).standard_normal((NS, R)) \
            .astype(np.float32)
        y = np.asarray(split_combine(jnp.asarray(part), interpret=True))
        np.testing.assert_allclose(y, part.sum(axis=0), rtol=1e-5,
                                   atol=1e-5)

    @pytest.mark.parametrize("T", [1, 13, 16])
    def test_tile_contrib_padded_tile_count(self, T):
        from repro.kernels.spmv_tile import tile_contrib
        rng = np.random.default_rng(T)
        data = rng.standard_normal((T, 8, 128)).astype(np.float32)
        xg = rng.standard_normal((T, 128)).astype(np.float32)
        out = np.asarray(tile_contrib(jnp.asarray(data), jnp.asarray(xg),
                                      interpret=True))
        np.testing.assert_allclose(out, np.einsum("tij,tj->ti", data, xg),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
class TestKernelProperties:
    @settings(max_examples=20, deadline=None)
    @given(M=st.sampled_from([8, 24, 64]),
           N=st.sampled_from([128, 256]),
           nnz=st.integers(10, 800),
           seed=st.integers(0, 2**16))
    def test_ell_linearity(self, M, N, nnz, seed):
        """SpMV is linear: A(ax + by) == a*Ax + b*Ay."""
        A, x = rand_problem(M, N, nnz, seed=seed)
        y2 = np.random.default_rng(seed + 1).standard_normal(N).astype(np.float32)
        e = csr_to_ell(A)
        data, cols = jnp.asarray(e.data), jnp.asarray(e.cols)
        f = lambda v: np.asarray(ref.ell_spmv_ref(data, cols, jnp.asarray(v)))
        lhs = f(2.0 * x + 3.0 * y2)
        np.testing.assert_allclose(lhs, 2.0 * f(x) + 3.0 * f(y2),
                                   rtol=1e-3, atol=1e-3)

    @settings(max_examples=15, deadline=None)
    @given(M=st.sampled_from([64, 256]), nnz=st.integers(16, 2000),
           seed=st.integers(0, 2**16))
    def test_seg_matches_ell_oracle(self, M, nnz, seed):
        """The segmented and ELL formats of one matrix agree on A @ x."""
        A, x = rand_problem(M, M, nnz, seed=seed)
        e = csr_to_ell(A)
        y_ell = np.asarray(ref.ell_spmv_ref(
            jnp.asarray(e.data), jnp.asarray(e.cols), jnp.asarray(x)))[:M]
        seg = ops.seg_from_csr(A)
        y_seg = np.asarray(ops.seg_spmv(seg, jnp.asarray(x)))
        np.testing.assert_allclose(y_seg, y_ell, rtol=1e-4, atol=1e-5)

    @settings(max_examples=15, deadline=None)
    @given(M=st.sampled_from([64, 256]), nnz=st.integers(16, 2000),
           ns=st.integers(1, 12), seed=st.integers(0, 2**16))
    def test_split_matches_float64_oracle(self, M, nnz, ns, seed):
        """Across arbitrary split counts, the two-stage split result
        matches the float64 numpy slab oracle and the seg family."""
        A, x = rand_problem(M, M, nnz, seed=seed)
        spl = ops.split_from_csr(A, ns)
        y = np.asarray(ops.split_spmv(spl, jnp.asarray(x)))
        y64 = _np_slab_oracle(spl.vals, spl.cols, spl.rows, x, M)
        np.testing.assert_allclose(y, y64, rtol=1e-4, atol=1e-4)
        y_seg = np.asarray(ops.seg_spmv(ops.seg_from_csr(A),
                                        jnp.asarray(x)))
        np.testing.assert_allclose(y, y_seg, rtol=1e-4, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(ns=st.integers(1, 8), seed=st.integers(0, 2**16))
    def test_split_batched_columns_independent(self, ns, seed):
        """(N, B) split oracle: each column equals its per-vector run."""
        A, _ = rand_problem(128, 128, 900, seed=seed)
        X = np.random.default_rng(seed).standard_normal((128, 2)) \
            .astype(np.float32)
        spl = ops.split_from_csr(A, ns)
        Y = np.asarray(ops.split_spmv(spl, jnp.asarray(X)))
        for b in range(2):
            np.testing.assert_allclose(
                Y[:, b],
                np.asarray(ops.split_spmv(spl, jnp.asarray(X[:, b]))),
                rtol=1e-5, atol=1e-5)

    @settings(max_examples=15, deadline=None)
    @given(nnz=st.integers(16, 600), seed=st.integers(0, 2**16))
    def test_tile_matches_ell_oracle(self, nnz, seed):
        """The bitmask-tiled and ELL formats of one matrix agree on
        A @ x across arbitrary sparsity draws."""
        A, x = rand_problem(128, 128, nnz, seed=seed)
        t = ops.tile_from_csr(A)
        y = np.asarray(ops.tile_spmv(t, jnp.asarray(x)))
        e = csr_to_ell(A)
        y_ell = np.asarray(ref.ell_spmv_ref(
            jnp.asarray(e.data), jnp.asarray(e.cols), jnp.asarray(x)))[:128]
        np.testing.assert_allclose(y, y_ell, rtol=1e-4, atol=1e-4)

    @settings(max_examples=15, deadline=None)
    @given(nnz=st.integers(16, 600), seed=st.integers(0, 2**16))
    def test_bell_zero_padding_is_noop(self, nnz, seed):
        """Padded (zero) blocks contribute nothing regardless of bcol."""
        A, x = rand_problem(128, 128, nnz, seed=seed)
        blocks, bcols = ops.bell_from_bcsr(csr_to_bcsr(A, (8, 128)))
        # scramble the bcol of padded slots — result must not change
        mask = np.abs(blocks).sum(axis=(2, 3)) == 0
        bcols2 = np.where(mask, (bcols + 1) % blocks.shape[0] // 128, bcols)
        r1 = ref.bell_spmv_ref(*map(jnp.asarray, (blocks, bcols, x)))
        r2 = ref.bell_spmv_ref(*map(jnp.asarray, (blocks, bcols2, x)))
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r2))
