"""The elementwise node's aval memo (ops/lazy.py ``_AVAL_MEMO``).

A ``LazyExpr`` learns its shape and dtype from ``jax.eval_shape``, and a frame
builds the same nodes request after request.  These tests pin the memo that
answers the repeats: it gives what a fresh ``eval_shape`` gives for every op,
dtype and scalar kind of the elementwise paths, traces nothing the second
time, keeps apart what ``eval_shape`` reads differently, passes past what its
key cannot describe, pins no operand, and stays within its bound.
"""

import gc
import threading
import weakref
from collections import OrderedDict

import jax
import numpy as np
import pytest

from modin_tpu.ops import lazy
from modin_tpu.ops.elementwise import get_op
from modin_tpu.parallel.mesh import replicated_sharding, row_sharding

_ROWS = 64

_DTYPES = ["int64", "int32", "float64", "float32", "bool"]

_SCALARS = {
    "py_int": 3,
    "py_float": 2.5,
    "np_int32": np.int32(3),
    "np_float32": np.float32(2.5),
}

_BINARY_OPS = ["add", "mul", "sub", "mod", "floordiv", "eq", "ne", "lt", "ge"]


@pytest.fixture(autouse=True)
def _fresh_memo(monkeypatch):
    """Each test starts from an empty memo of its own."""
    monkeypatch.setattr(lazy, "_AVAL_MEMO", OrderedDict())


def _column(dtype, rows=_ROWS, sharding=None):
    host = np.arange(rows) % 7
    host = host.astype(bool) if dtype == "bool" else host.astype(dtype)
    return jax.device_put(host, sharding if sharding is not None else row_sharding())


def _fresh(op, args, static=()):
    fn = get_op(op)
    kw = dict(static)
    return jax.eval_shape(lambda *xs: fn(*xs, **kw), *args)


def _memo_answers(op, args, static=()):
    """The aval of a node built twice: once traced and kept, once from the
    memo; both are returned, with the memo's size between them."""
    first = lazy.lazy_op(op, *args, static=static).aval
    kept = len(lazy._AVAL_MEMO)
    second = lazy.lazy_op(op, *args, static=static).aval
    assert len(lazy._AVAL_MEMO) == kept == 1
    return first, second


@pytest.mark.parametrize("scalar", list(_SCALARS))
@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize("op", _BINARY_OPS)
def test_binary_node_aval_equals_a_fresh_eval_shape(op, dtype, scalar):
    args = (_column(dtype), _SCALARS[scalar])
    first, second = _memo_answers(op, args)
    assert first == second == _fresh(op, args)


@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize(
    "op, static",
    [
        ("abs", ()),
        ("astype", (("dtype", "float64"),)),
        ("astype", (("dtype", "int32"),)),
        ("round", (("decimals", 2),)),
    ],
)
def test_unary_node_aval_equals_a_fresh_eval_shape(op, static, dtype):
    args = (_column(dtype),)
    first, second = _memo_answers(op, args, static)
    assert first == second == _fresh(op, args, static)


@pytest.mark.parametrize("dtype", _DTYPES)
def test_isin_node_aval_equals_a_fresh_eval_shape(dtype):
    values = jax.device_put(np.array([1, 4, 6]).astype(dtype), replicated_sharding())
    args = (_column(dtype), values)
    first, second = _memo_answers("isin_vals", args)
    assert first == second == _fresh("isin_vals", args)


def test_a_chain_is_keyed_by_its_child_aval():
    col = _column("int64")
    inner = lazy.lazy_op("mul", col, 2)
    outer = lazy.lazy_op("add", inner, 1.5)
    again = lazy.lazy_op("add", lazy.lazy_op("mul", col, 2), 1.5)
    assert len(lazy._AVAL_MEMO) == 2
    assert again.aval == outer.aval == _fresh("add", (inner.aval, 1.5))


def test_a_materialized_child_is_keyed_as_its_result():
    col = _column("int64")
    child = lazy.lazy_op("mul", col, 2)
    lazy.materialize_exprs([child])
    assert lazy._aval_key("add", (child, 1), ()) == lazy._aval_key(
        "add", (child._result, 1), ()
    )


@pytest.fixture
def eval_shape_calls(monkeypatch):
    calls = []
    real = jax.eval_shape

    def counting(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(jax, "eval_shape", counting)
    return calls


@pytest.mark.parametrize(
    "build",
    [
        pytest.param(lambda col: lazy.lazy_op("add", col, 2), id="scalar"),
        pytest.param(lambda col: lazy.lazy_op("abs", col), id="unary"),
        pytest.param(
            lambda col: lazy.lazy_op("mod", lazy.lazy_op("mul", col, col), 7),
            id="chain",
        ),
        pytest.param(
            lambda col: lazy.lazy_op("round", col.astype("float64"), static=(("decimals", 1),)),
            id="static",
        ),
    ],
)
def test_building_the_same_node_again_traces_nothing(build, eval_shape_calls):
    col = _column("int64")
    build(col)
    assert eval_shape_calls  # the first build traced
    eval_shape_calls.clear()
    build(col)
    build(_column("int64"))  # another column of the same aval
    assert eval_shape_calls == []


@pytest.mark.parametrize(
    "a, b",
    [
        pytest.param(
            lambda: ("add", (_column("int64"), 2), ()),
            lambda: ("add", (_column("int64"), np.int64(2)), ()),
            id="weak-vs-strong-scalar",
        ),
        pytest.param(
            lambda: ("add", (_column("int64"), 2), ()),
            lambda: ("add", (_column("int64"), 2.0), ()),
            id="int-vs-float-scalar",
        ),
        pytest.param(
            lambda: ("add", (_column("int64"), 2), ()),
            lambda: ("add", (_column("int64", rows=2 * _ROWS), 2), ()),
            id="shape",
        ),
        pytest.param(
            lambda: ("add", (_column("int64"), 2), ()),
            lambda: ("add", (_column("int32"), 2), ()),
            id="dtype",
        ),
        pytest.param(
            lambda: ("round", (_column("float64"),), (("decimals", 1),)),
            lambda: ("round", (_column("float64"),), (("decimals", 2),)),
            id="static",
        ),
        pytest.param(
            lambda: ("add", (_column("int64"), 2), ()),
            lambda: ("add", (_column("int64", sharding=replicated_sharding()), 2), ()),
            id="row-sharded-vs-replicated",
        ),
        pytest.param(
            lambda: ("add", (_column("int64"), 2), ()),
            lambda: ("mul", (_column("int64"), 2), ()),
            id="op",
        ),
    ],
)
def test_what_eval_shape_reads_differently_gets_its_own_key(a, b):
    assert len(jax.devices()) == 8  # the suite's virtual mesh
    first, second = a(), b()
    key_a, key_b = lazy._aval_key(*first), lazy._aval_key(*second)
    assert key_a is not None and key_b is not None
    assert key_a != key_b
    for op, args, static in (first, second):
        assert lazy.lazy_op(op, *args, static=static).aval == _fresh(op, args, static)
    assert len(lazy._AVAL_MEMO) == 2


def test_eval_shape_carries_no_sharding():
    """The memo's avals are layout-free on this JAX: a row-sharded and a
    replicated column give one ``ShapeDtypeStruct``.  Should ``eval_shape``
    start to return a sharding, the key must also hold the mesh epoch
    (``_cache_epoch_key``) so a re-seat never answers a stale layout."""
    sharded = _fresh("add", (_column("int64"), 2))
    replicated = _fresh("add", (_column("int64", sharding=replicated_sharding()), 2))
    assert sharded.sharding is None and sharded == replicated


@pytest.mark.parametrize(
    "args, static, raises",
    [
        pytest.param(lambda: (_column("int64"), 2**64), (), OverflowError, id="int-past-int64"),
        pytest.param(lambda: (_column("int64"), -(2**63) - 1), (), OverflowError, id="int-under-int64"),
        pytest.param(
            lambda: (_column("float64"),), (("decimals", [1]),), TypeError, id="unhashable-static"
        ),
        pytest.param(lambda: (_column("int64"), np.arange(_ROWS)), (), None, id="numpy-array-leaf"),
    ],
)
def test_what_the_key_cannot_describe_is_traced_past_the_memo(args, static, raises):
    """``None`` from the key: the node is traced as before the memo, and its
    failure, if any, is the trace's own."""
    op = "round" if static else "add"
    built = args()
    assert lazy._aval_key(op, built, static) is None
    if raises is not None:
        with pytest.raises(raises):
            lazy.lazy_op(op, *built, static=static)
    else:
        assert lazy.lazy_op(op, *built, static=static).aval.shape == (_ROWS,)
    assert len(lazy._AVAL_MEMO) == 0


def test_the_int64_edges_are_keyed():
    col = _column("int64")
    for edge in (2**63 - 1, -(2**63)):
        assert lazy._aval_key("add", (col, edge), ()) is not None
        assert lazy.lazy_op("add", col, edge).aval == _fresh("add", (col, edge))


def test_the_memo_pins_no_operand():
    col = _column("int64")
    child = lazy.lazy_op("mul", col, 2)
    node = lazy.lazy_op("add", child, col)
    ref = weakref.ref(col)
    assert len(lazy._AVAL_MEMO) == 2
    del col, child, node
    gc.collect()
    assert ref() is None
    for key, aval in lazy._AVAL_MEMO.items():
        assert isinstance(aval, jax.ShapeDtypeStruct)
        assert not any(isinstance(x, (jax.Array, lazy.LazyExpr)) for x in _flatten(key))


def _flatten(x):
    if isinstance(x, tuple):
        for item in x:
            yield from _flatten(item)
    else:
        yield x


def test_the_memo_is_bounded_and_least_recently_used(monkeypatch):
    monkeypatch.setattr(lazy, "_AVAL_MEMO_SIZE", 3)
    cols = [_column("int64", rows=_ROWS * (i + 1)) for i in range(4)]
    for col in cols[:3]:
        lazy.lazy_op("add", col, 1)
    lazy.lazy_op("add", cols[0], 1)  # the oldest, used again
    lazy.lazy_op("add", cols[3], 1)  # evicts cols[1]'s entry
    assert len(lazy._AVAL_MEMO) == 3
    kept = {key[2][0][2] for key in lazy._AVAL_MEMO}
    assert kept == {(_ROWS,), (_ROWS * 3,), (_ROWS * 4,)}


def test_concurrent_builders_agree():
    cols = {dtype: _column(dtype) for dtype in _DTYPES}
    expect = {dtype: _fresh("mul", (col, 2)) for dtype, col in cols.items()}
    errors = []

    def build():
        try:
            for _ in range(20):
                for dtype, col in cols.items():
                    assert lazy.lazy_op("mul", col, 2).aval == expect[dtype]
        except Exception as exc:  # surfaced below, on the test's thread
            errors.append(exc)

    threads = [threading.Thread(target=build) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert len(lazy._AVAL_MEMO) == len(_DTYPES)
