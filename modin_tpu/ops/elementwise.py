"""Elementwise device kernels: maps and binary ops over column sets.

TPU-native replacement for the reference's Map/Binary operators over block
partitions (modin/core/dataframe/algebra/map.py:28, binary.py:293): instead of
one task per partition, ALL device columns go through ONE jit call as a
pytree, so XLA fuses the whole frame-wide expression and the dispatch cost is
paid once (per-call dispatch overhead, not bandwidth, bounds small ops).

Pandas semantic deltas handled here:
- int / int true-division promotes to float64 and yields +/-inf on zero
  division (numpy raises/warns; jnp matches IEEE, which is what pandas does);
- int floordiv/mod with a zero divisor promotes to float64 (inf/nan) in
  pandas 3 — a data-dependent dtype, so the QC gates those cases to the
  pandas fallback; the kernels' zero-masking only backstops traced scalar
  divisors that are known nonzero at dispatch.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Tuple

import numpy as np
from modin_tpu.observability import meters as _meters
from modin_tpu.ops._program import named_jit, traced_jit

#: the registry's names that take :func:`_divmod`
_DIVMOD_OPS = frozenset({"mod", "rmod", "floordiv", "rfloordiv"})


def _trim(x, p_out):
    """Slice a padded column down to a smaller padded size, keeping the
    rows axis sharded (a bare slice can come back replicated)."""
    import jax

    from modin_tpu.parallel.mesh import row_sharding

    return jax.lax.with_sharding_constraint(x[:p_out], row_sharding())


_INT32_MIN = -(2**31)
# A float32 estimate of a quotient is scaled by this before it is truncated:
# the two conversions, the reciprocal and the two products each round by
# 2**-24 or a few of them, and their sum stays well under 2**-20 (a v5e reads
# 4.2 x 2**-24 at worst: PERF.md, PR 37), so the estimate never passes the true
# quotient and a remainder never goes negative.
_NEVER_OVER = 1.0 - 2.0**-20


def _narrow_divmod(x, y):
    """Exact floor quotient and remainder of int32 ``x`` by int32 ``y`` with
    no integer divide (a TPU has none; XLA expands one into a long division).

    Needs ``|x|, |y| <= 2**31 - 1`` and ``y != 0``.  Two float32 estimates
    that never overshoot leave a quotient of at most 1 (the second round
    starts under ``1 + 2**12 / |y|``), so the two conditional subtractions
    have one to spare; then the signs of floor division.
    """
    import jax.numpy as jnp

    ax, ay = abs(x), abs(y)
    inv = (1.0 / ay.astype(jnp.float32)) * jnp.float32(_NEVER_OVER)
    q = (ax.astype(jnp.float32) * inv).astype(jnp.int32)
    r = ax - q * ay
    est = (r.astype(jnp.float32) * inv).astype(jnp.int32)
    q, r = q + est, r - est * ay
    for _ in range(2):
        over = r >= ay
        q, r = q + over, jnp.where(over, r - ay, r)
    differ = (x < 0) != (y < 0)
    borrow = differ & (r != 0)
    q = jnp.where(differ, -q, q) - borrow
    r = jnp.where(borrow, ay - r, r)
    return q, jnp.where(y < 0, -r, r)


def _fits_narrow(v):
    """Every element of int64 ``v`` is its own low word sign-extended, and
    not ``-2**31`` (whose magnitude and whose quotient by -1 pass int32)."""
    import jax.numpy as jnp

    low = v.astype(jnp.int32)
    return jnp.all((low.astype(jnp.int64) == v) & (low != _INT32_MIN))


def _plain_divmod(x, y, want: str):
    return x % y if want == "mod" else x // y


def _zero_safe_divmod(x, y, want: str):
    """Integer ``x % y`` / ``x // y``, 0 where ``y`` is 0 (module docstring)."""
    import jax.numpy as jnp

    zero = y == 0
    return jnp.where(zero, 0, _plain_divmod(x, jnp.where(zero, 1, y), want))


def _guarded_divmod(x, y, want: str):
    """int64 ``x % y`` (``want="mod"``) or ``x // y`` (``"floordiv"``), 0
    where ``y`` is 0: the program looks at its own operands and divides in 32
    bits where every one of them fits, in 64 as before where one does not.

    pandas' integers are int64 whatever they hold, and a 64-bit remainder
    costs a TPU some 1800 VPU operations a row; :func:`_narrow_divmod` some
    40.  Both branches write the answer, zero rule included, so the
    conditional's output is the program's and not a temporary beside it.
    """
    import jax
    import jax.numpy as jnp

    x, y = x.astype(jnp.int64), y.astype(jnp.int64)

    def narrow(x, y):
        y = y.astype(jnp.int32)
        zero = y == 0
        q, r = _narrow_divmod(x.astype(jnp.int32), jnp.where(zero, 1, y))
        return jnp.where(zero, 0, r if want == "mod" else q).astype(jnp.int64)

    wide = functools.partial(_zero_safe_divmod, want=want)
    return jax.lax.cond(_fits_narrow(x) & _fits_narrow(y), narrow, wide, x, y)


@functools.lru_cache(maxsize=None)
def _jit_guarded_divmod(want: str):
    # jitted so that eval_shape of a node and the trace of a fused plan see
    # one call, cached by aval, where the body's hundred equations would be
    return traced_jit(
        functools.partial(_guarded_divmod, want=want), f"guarded_{want}"
    )


def _divmod(x, y, want: str):
    """The registry's ``mod`` / ``floordiv`` (and, operands swapped, their
    reflections): integers answer 0 for a zero divisor, see the module's
    docstring; an int64 result takes :func:`_guarded_divmod`."""
    import jax.numpy as jnp

    res_dtype = jnp.result_type(x, y)
    if res_dtype == np.dtype(np.int64):
        return _jit_guarded_divmod(want)(x, y)
    if jnp.issubdtype(res_dtype, jnp.integer):
        return _zero_safe_divmod(x, y, want)
    return _plain_divmod(x, y, want)


def _truediv(x, y):
    import jax.numpy as jnp

    res_dtype = jnp.result_type(x, y)
    if jnp.issubdtype(res_dtype, jnp.integer) or res_dtype == jnp.bool_:
        x = x.astype(jnp.float64) if hasattr(x, "astype") else jnp.float64(x)
    return x / y


def _build_ops() -> dict:
    import jax.numpy as jnp

    return {
        "add": lambda x, y: x + y,
        "radd": lambda x, y: y + x,
        "sub": lambda x, y: x - y,
        "rsub": lambda x, y: y - x,
        "mul": lambda x, y: x * y,
        "rmul": lambda x, y: y * x,
        "truediv": _truediv,
        "rtruediv": lambda x, y: _truediv(y, x) if not np.isscalar(y) else _truediv(jnp.asarray(y), x),
        "floordiv": lambda x, y: _divmod(x, y, "floordiv"),
        "rfloordiv": lambda x, y: _divmod(y, x, "floordiv"),
        "mod": lambda x, y: _divmod(x, y, "mod"),
        "rmod": lambda x, y: _divmod(y, x, "mod"),
        "pow": lambda x, y: x ** y,
        "rpow": lambda x, y: y ** x,
        "eq": lambda x, y: x == y,
        "ne": lambda x, y: x != y,
        "lt": lambda x, y: x < y,
        "le": lambda x, y: x <= y,
        "gt": lambda x, y: x > y,
        "ge": lambda x, y: x >= y,
        "__and__": lambda x, y: x & y,
        "__or__": lambda x, y: x | y,
        "__xor__": lambda x, y: x ^ y,
        "__rand__": lambda x, y: y & x,
        "__ror__": lambda x, y: y | x,
        "__rxor__": lambda x, y: y ^ x,
        # membership against a runtime value ARRAY (one compile per list
        # length, values stay jit arguments); the _nan variant adds pandas'
        # NaN-matches-NaN rule when the value list contains NaN
        "isin_vals": lambda x, v: jnp.isin(x, v),
        "isin_vals_nan": lambda x, v: jnp.isin(x, v) | jnp.isnan(x),
        # unary
        "abs": lambda x: abs(x),
        "negative": lambda x: -x,
        "invert": lambda x: ~x,
        "isna": lambda x: jnp.isnan(x) if jnp.issubdtype(x.dtype, jnp.floating) else jnp.zeros(x.shape, bool),
        "notna": lambda x: ~jnp.isnan(x) if jnp.issubdtype(x.dtype, jnp.floating) else jnp.ones(x.shape, bool),
        "sqrt": lambda x: jnp.sqrt(x),
        "exp": lambda x: jnp.exp(x),
        "log": lambda x: jnp.log(x),
        "log2": lambda x: jnp.log2(x),
        "log10": lambda x: jnp.log10(x),
        "sin": lambda x: jnp.sin(x),
        "cos": lambda x: jnp.cos(x),
        "tan": lambda x: jnp.tan(x),
        "tanh": lambda x: jnp.tanh(x),
        "floor": lambda x: jnp.floor(x),
        "ceil": lambda x: jnp.ceil(x),
        "sign": lambda x: jnp.sign(x),
        # cumulative ops with pandas skipna semantics: NaN keeps its position
        # but does not poison later entries
        "cumsum": lambda x: _nan_skipping_cum(x, jnp.cumsum, 0),
        "cumprod": lambda x: _nan_skipping_cum(x, jnp.cumprod, 1),
        "cummax": lambda x: _nan_skipping_cum(x, jax_lax_cummax, -jnp.inf),
        "cummin": lambda x: _nan_skipping_cum(x, jax_lax_cummin, jnp.inf),
        # physical resize to the padded-output invariant after a device
        # compaction (ops/structural.py); p_out is compiled into the program
        "trim": _trim,
        "round": lambda x, decimals: (
            jnp.round(x, decimals) if jnp.issubdtype(x.dtype, jnp.floating) else x
        ),
        "astype": lambda x, dtype: x.astype(dtype),
        "isna_nat": lambda x: x == _NAT_SENTINEL,
        "notna_nat": lambda x: x != _NAT_SENTINEL,
        "fillna": lambda x, v: (
            jnp.where(jnp.isnan(x), v, x) if jnp.issubdtype(x.dtype, jnp.floating) else x
        ),
        "clip_lower": lambda x, lo: jnp.where(x < lo, lo, x),
        "clip_upper": lambda x, hi: jnp.where(x > hi, hi, x),
    }


def _nan_skipping_cum(x, cum_fn, neutral):
    import jax.numpy as jnp

    if not jnp.issubdtype(x.dtype, jnp.floating):
        return cum_fn(x)
    nanm = jnp.isnan(x)
    filled = cum_fn(jnp.where(nanm, neutral, x))
    return jnp.where(nanm, jnp.nan, filled)


def jax_lax_cummax(x):
    import jax.lax as lax

    return lax.cummax(x, axis=0)


def jax_lax_cummin(x):
    import jax.lax as lax

    return lax.cummin(x, axis=0)


_OPS: dict = {}


def _ensure_ops() -> None:
    global _OPS
    if not _OPS:
        _OPS.update(_build_ops())


def get_op(op_name: str) -> Callable:
    """Elementwise op registry accessor (used by the lazy fusion layer)."""
    _ensure_ops()
    return _OPS[op_name]


def binary_op_columns(op_name: str, cols: List[Any], other: Any) -> List[Any]:
    """Deferred binary op on device columns vs a scalar or matching columns.

    Returns :class:`~modin_tpu.ops.lazy.LazyExpr` nodes: nothing dispatches
    until a consumer needs concrete data, at which point the whole
    accumulated chain compiles as one fused jit (ops/lazy.py).
    """
    from modin_tpu.ops.lazy import lazy_op

    _ensure_ops()
    if isinstance(other, (list, tuple)):
        out = [lazy_op(op_name, c, o) for c, o in zip(cols, other)]
    else:
        out = [lazy_op(op_name, c, other) for c in cols]
    if _meters.ACCOUNTING_ON and op_name in _DIVMOD_OPS:
        guarded = sum(e.dtype == np.int64 for e in out)
        if guarded:
            _meters.note_elementwise_form("divmod_guarded", guarded)
    return out


def unary_op_columns(op_name: str, cols: List[Any]) -> List[Any]:
    """Deferred unary op on device columns (see binary_op_columns)."""
    from modin_tpu.ops.lazy import lazy_op

    _ensure_ops()
    return [lazy_op(op_name, c) for c in cols]


_NAT_SENTINEL = np.iinfo(np.int64).min


def isna_columns(cols: List[Any], mM_flags: Tuple[bool, ...], negate: bool) -> List[Any]:
    """Deferred isna/notna, NaT-sentinel-aware for datetime-backed columns."""
    from modin_tpu.ops.lazy import lazy_op

    _ensure_ops()
    out = []
    for c, is_dt in zip(cols, mM_flags):
        if is_dt:
            out.append(lazy_op("notna_nat" if negate else "isna_nat", c))
        else:
            out.append(lazy_op("notna" if negate else "isna", c))
    return out


def round_columns(cols: List[Any], decimals: int) -> List[Any]:
    from modin_tpu.ops.lazy import lazy_op

    _ensure_ops()
    static = (("decimals", int(decimals)),)
    return [lazy_op("round", c, static=static) for c in cols]


def fillna_columns(cols: List[Any], value: Any) -> List[Any]:
    from modin_tpu.ops.lazy import lazy_op

    _ensure_ops()
    return [lazy_op("fillna", c, value) for c in cols]


def clip_columns(cols: List[Any], lower: Any, upper: Any) -> List[Any]:
    from modin_tpu.ops.lazy import lazy_op

    _ensure_ops()
    out = []
    for c in cols:
        r = c
        if lower is not None:
            r = lazy_op("clip_lower", r, lower)
        if upper is not None:
            r = lazy_op("clip_upper", r, upper)
        out.append(r)
    return out


@functools.lru_cache(maxsize=None)
def _jit_shift(n_cols: int, n: int, periods: int, as_diff: bool):
    import jax
    import jax.numpy as jnp

    def one(c):
        k = abs(periods)
        if k == 0:
            if as_diff:
                # pandas diff(0) still promotes ints to float64
                return (c - c).astype(jnp.float64)
            return c  # shift(0) preserves the dtype
        if k >= n:
            # pandas: shifting past the frame is all-NaN (diff likewise)
            return jnp.full(c.shape, jnp.nan, jnp.float64)
        is_f = jnp.issubdtype(c.dtype, jnp.floating)
        x = c.astype(jnp.float64) if not is_f else c
        if periods >= 0:
            shifted = jnp.concatenate(
                [jnp.full(k, jnp.nan, x.dtype), x[: x.shape[0] - k]]
            )
        else:
            shifted = jnp.concatenate([x[k:], jnp.full(k, jnp.nan, x.dtype)])
            # mask the region beyond the logical length: rows shifted in from
            # pads must read as missing
            valid_src = jnp.arange(x.shape[0]) + k < n
            shifted = jnp.where(valid_src, shifted, jnp.nan)
        if as_diff:
            return x - shifted
        return shifted

    def fn(cols: Tuple) -> Tuple:
        return tuple(one(c) for c in cols)

    return named_jit(fn, "elementwise_shift")


def shift_columns(cols: List[Any], n: int, periods: int) -> List[Any]:
    """pandas shift: rows move by ``periods`` with NaN fill (float64 result)."""
    return list(_jit_shift(len(cols), int(n), int(periods), False)(tuple(cols)))


def diff_columns(cols: List[Any], n: int, periods: int) -> List[Any]:
    """pandas diff: x - x.shift(periods) (float64 result)."""
    return list(_jit_shift(len(cols), int(n), int(periods), True)(tuple(cols)))


def astype_column(col: Any, target: np.dtype) -> Any:
    import jax.numpy as jnp

    return col.astype(jnp.dtype(target))

