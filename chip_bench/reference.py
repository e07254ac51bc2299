"""The plain reference the served answers are judged against, its control,
and the comparison that decides ``correct``.

The reference is ``y = A x`` in float64 straight off the benchmark's own CSR
arrays, one column at a time.  An answer is judged row by row against the
size of the row's own terms: ``|y - y_ref|_i / (|A| |x|)_i``.  That scale
is what float32 accumulation over a long row can be expected to miss by,
so long and short rows are held to the same relative standard.

The control is the step below the configuration's float32: the same
products with the values and x rounded to bfloat16 (summed in float32), the
precision a later change that halves the bytes of the values would serve
in.  It has to fail the comparison.
"""
from __future__ import annotations

import numpy as np

from chip_bench.matrix import Csr

__all__ = ["Reference", "bf16_control"]


class Reference:
    """float64 answers and row scales of one matrix, per request vector."""

    def __init__(self, A: Csr):
        self.A = A
        self._rows = A.row_ids()
        self._abs = np.abs(A.values)

    def _matvec(self, values: np.ndarray, x: np.ndarray) -> np.ndarray:
        return np.bincount(self._rows, weights=values * x[self.A.col_index],
                           minlength=self.A.nrows)

    def answer(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(y_ref, scale) for x of shape (n,) or (n, B), in float64."""
        x = np.asarray(x, dtype=np.float64)
        cols = [x] if x.ndim == 1 else list(x.T)
        y = [self._matvec(self.A.values, c) for c in cols]
        s = [self._matvec(self._abs, np.abs(c)) for c in cols]
        if x.ndim == 1:
            return y[0], s[0]
        return np.stack(y, axis=1), np.stack(s, axis=1)

    @staticmethod
    def norm_err(y, y_ref: np.ndarray, scale: np.ndarray) -> float:
        """max_i |y - y_ref|_i / scale_i; inf for a wrong shape, a
        non-finite entry, or a non-zero answer where the scale is 0."""
        y = np.asarray(y, dtype=np.float64)
        if y.shape != y_ref.shape or not np.isfinite(y).all():
            return float("inf")
        err = np.abs(y - y_ref)
        zero = scale == 0
        if (err[zero] > 0).any():
            return float("inf")
        return float(np.max(err[~zero] / scale[~zero], initial=0.0))


def bf16_control(A: Csr, xs: np.ndarray, device=None) -> np.ndarray:
    """The control's answers for the vectors ``xs`` (k, n) or blocks
    (k, n, B): values and x rounded to bfloat16, products summed in
    float32 by a segment sum on ``device`` (the first device if None)."""
    import jax
    import jax.numpy as jnp

    device = device or jax.devices()[0]
    put = lambda a: jax.device_put(a, device)
    vals = put(jnp.asarray(A.values, jnp.bfloat16))
    cols = put(A.col_index)
    rows = put(A.row_ids())

    @jax.jit
    def one(vals, cols, rows, x):
        prod = vals.astype(jnp.float32) * \
            x.astype(jnp.bfloat16).astype(jnp.float32)[cols]
        return jax.ops.segment_sum(prod, rows, num_segments=A.nrows,
                                   indices_are_sorted=True)

    out = []
    for x in xs:
        x = put(np.asarray(x, np.float32))
        if x.ndim == 1:
            out.append(np.asarray(one(vals, cols, rows, x)))
        else:
            out.append(np.stack([np.asarray(one(vals, cols, rows, x[:, b]))
                                 for b in range(x.shape[1])], axis=1))
    return np.stack(out)
