"""Pairwise statistics kernels: corr / cov as masked matmuls.

Reference design: modin/core/storage_formats/pandas/aggregations.py:31
(CorrCovBuilder) computes per-block sums-of-products then combines across
partitions.  On TPU the whole thing is three matmuls on the MXU: with
Z = values (NaN→0) and V = validity masks, every pairwise-complete sum the
Pearson formula needs is a (k x n) @ (n x k) product —

    N  = Vᵀ V         pairwise-complete counts
    S  = Zᵀ V         per-pair sums  (S[i,j] = Σ x_i over rows valid in both)
    P  = Zᵀ Z         per-pair product sums
    Q  = (Z∘Z)ᵀ V     per-pair square sums

— so the n-row scan is entirely MXU work and the k x k combine is elementwise.
pandas semantics: pairwise-complete observations, min_periods gating, NaN
where a pair has no (or insufficient) data.
"""

from __future__ import annotations

import functools
from typing import Any, List, Tuple

import numpy as np


from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.ops._program import named_jit


@functools.lru_cache(maxsize=None)
def _jit_corr_cov(
    method: str, n_cols: int, n: int, ddof: int, min_periods: int
):
    import jax
    import jax.numpy as jnp

    def fn(cols: Tuple):
        X = jnp.stack([c.astype(jnp.float64) for c in cols], axis=1)  # (P, k)
        valid_rows = (jnp.arange(X.shape[0]) < n)[:, None]
        V = (~jnp.isnan(X)) & valid_rows
        Z = jnp.where(V, X, 0.0)
        Vf = V.astype(jnp.float64)

        N = Vf.T @ Vf                     # pairwise-complete counts
        S = Z.T @ Vf                      # S[i, j] = sum x_i over both-valid
        P = Z.T @ Z                       # sum x_i * x_j
        Q = (Z * Z).T @ Vf                # sum x_i^2 over both-valid

        Nsafe = jnp.maximum(N, 1.0)
        # pandas quirk: with any NaN present, DataFrame.cov takes the
        # pairwise-complete path which always divides by N-1, ignoring ddof
        has_nan = jnp.any(jnp.isnan(X) & valid_rows)
        eff_ddof = jnp.where(has_nan, 1.0, float(ddof))
        # pairwise covariance: E[xy] - E[x]E[y], scaled by (N - ddof)
        cov = (P - S * S.T / Nsafe) / jnp.maximum(N - eff_ddof, 1.0)
        if method == "cov":
            out = jnp.where(N - eff_ddof > 0, cov, jnp.nan)
        else:
            var_i = (Q - S * S / Nsafe) / jnp.maximum(N - ddof, 1.0)
            var_j = var_i.T
            denom = jnp.sqrt(var_i * var_j)
            out = jnp.where(denom > 0, cov / denom, jnp.nan)
            out = jnp.clip(out, -1.0, 1.0)
        out = jnp.where(N >= max(min_periods, 1), out, jnp.nan)
        return out, N

    return named_jit(fn, "stats_corr_cov")


def corr_cov_matrix(
    cols: List[Any],
    n: int,
    method: str = "corr",
    ddof: int = 1,
    min_periods: int = 1,
) -> Tuple[np.ndarray, np.ndarray]:
    """(k x k matrix, pairwise counts) on host for the given device columns."""
    import jax

    fn = _jit_corr_cov(method, len(cols), int(n), int(ddof), int(min_periods))
    out, counts = fn(tuple(cols))
    out_h, counts_h = _engine_materialize((out, counts))
    return np.asarray(out_h), np.asarray(counts_h)
