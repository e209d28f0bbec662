"""``TpuQueryCompiler`` — the device-native query compiler.

TPU-native counterpart of the reference's PandasQueryCompiler
(modin/core/storage_formats/pandas/query_compiler.py:279): inherits the full
default-to-pandas surface from BaseQueryCompiler (correctness floor) and
overrides the hot subset with sharded jax.Array implementations:

- elementwise maps and binary ops  -> one jit over all device columns (XLA
  fuses across columns; the reference's ``map_partitions`` without task
  overhead)
- axis reductions                  -> jnp reduce; XLA emits psum over ICI
  when the array is sharded (the reference's ``tree_reduce``)
- groupby reductions               -> segment-sum on factorized keys (the
  reference's ``groupby_reduce`` map+reduce pair collapses into one kernel)
- sort/gather/filter/concat        -> device argsort/take/concatenate

Operations it can't run on device (object dtypes, exotic kwargs) fall through
to the inherited defaults, exactly the reference's incremental-optimization
strategy (SURVEY.md §7 stage 2).
"""

from __future__ import annotations

import re
from typing import Any, Hashable, List, NamedTuple, Optional

import numpy as np
import pandas

from modin_tpu.config import BenchmarkMode
from modin_tpu.core.dataframe.tpu.dataframe import (
    DeviceColumn,
    HostColumn,
    TpuDataframe,
)
from modin_tpu.core.dataframe.tpu.metadata import LazyIndex
from modin_tpu.core.execution.resilience import device_path
from modin_tpu.logging import disable_logging
from modin_tpu.core.storage_formats.base.query_compiler import (
    BaseQueryCompiler,
    QCCoercionCost,
)
from modin_tpu.utils import MODIN_UNNAMED_SERIES_LABEL

# below this, one host gather is cheaper than the shuffle + chunked fetches
_SHUFFLE_APPLY_MIN_ROWS = 1 << 19


from modin_tpu.observability import spans as _spans
from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.parallel.engine import upload as _engine_upload
from modin_tpu.plan import explain as graftplan_explain
from modin_tpu.plan import runtime as graftplan
from modin_tpu import streaming as graftstream
from modin_tpu import views as graftview


class _GroupbyParts(NamedTuple):
    """The kernels' answer to a device groupby before it is given the caller's
    shape (``TpuQueryCompiler._groupby_parts`` / ``_assemble_groupby``)."""

    datas: list  # a device array a value column: G rows in key order, padded
    out_dtypes: list
    value_labels: pandas.Index
    value_decoders: list  # (categories, dtype) where a result is a dictionary code
    codes: Any  # factorize_keys' row codes
    n_groups: int
    group_keys: list  # a host array a key level, in key order
    key_labels: list
    key_decoders: list  # None | ("cat", CategoricalDtype) | dictionary categories
    key_dtypes: list  # the key columns' own dtypes


def _decide_windowed(op: str, frames: tuple) -> bool:
    """graftstream residency verdict for an op over concrete frames (the
    caller has already checked the ``STREAM_ON`` fast path)."""
    from modin_tpu.ops import router
    from modin_tpu.streaming import windows as stream_windows

    est = sum(stream_windows.frame_nbytes(f) for f in frames)
    resident = sum(stream_windows.frame_resident_bytes(f) for f in frames)
    return router.decide_residency(op, est, resident) == "windowed"


def _named_aggs(f: Any) -> bool:
    """A dict ``agg``'s value the device path takes: an aggregation's name, or
    a list of distinct names."""
    if isinstance(f, str):
        return True
    return (
        isinstance(f, list)
        and bool(f)
        and all(isinstance(x, str) for x in f)
        and len(set(f)) == len(f)
    )


class TpuQueryCompiler(BaseQueryCompiler):
    """Query compiler over a TpuDataframe (sharded jax.Array columns).

    graftplan deferred mode: a compiler built by :meth:`from_plan` carries a
    pending logical plan (``_plan``) instead of a frame.  Plan-capable
    methods carry a one-line guard that extends the plan; every other method
    reaches ``_modin_frame``, whose property getter materializes the plan
    (optimize + lower through the eager seams) on first touch — so "any op
    with no plan node" is a materialization point by construction, and
    ``MODIN_TPU_PLAN=Off`` (no plans ever built) is bit-for-bit today's
    eager behavior.
    """

    storage_format = property(lambda self: "Tpu")
    engine = property(lambda self: "Jax")

    @disable_logging  # field assignments: one to three a request
    def __init__(self, frame: TpuDataframe, shape_hint: Optional[str] = None):
        assert isinstance(frame, TpuDataframe), type(frame)
        self._frame = frame
        self._plan = None
        self._shape_hint = shape_hint

    @classmethod
    def from_plan(cls, plan: Any, shape_hint: Optional[str] = None) -> "TpuQueryCompiler":
        """Build a deferred compiler over a pending graftplan node."""
        self = cls.__new__(cls)
        self._frame = None
        self._plan = plan
        self._shape_hint = shape_hint
        return self

    @property
    def _modin_frame(self) -> TpuDataframe:
        frame = self._frame
        if frame is None:
            frame = graftplan.force(self)
        return frame

    @_modin_frame.setter
    def _modin_frame(self, frame: TpuDataframe) -> None:
        self._frame = frame
        self._plan = None

    def eager_snapshot(self) -> "TpuQueryCompiler":
        """An eager compiler over this one's (materialized) frame."""
        return TpuQueryCompiler(self._modin_frame, self._shape_hint)

    def explain(self, analyze: bool = False) -> str:
        """graftplan EXPLAIN: the logical plan before/after rewrite.

        ``analyze=True`` (EXPLAIN ANALYZE) executes the plan — a pending
        plan materializes into this compiler, bit-exact vs plain execution
        — and annotates every node with measured wall time, rows, bytes,
        and dispatch count, followed by the graftmeter per-query rollup.
        """
        return graftplan_explain.explain_qc(self, analyze=analyze)

    # ------------------------------------------------------------------ #
    # Data exchange
    # ------------------------------------------------------------------ #

    @classmethod
    def from_pandas(cls, df: pandas.DataFrame, data_cls: Any = None) -> "TpuQueryCompiler":
        return cls(TpuDataframe.from_pandas(df))

    def to_pandas(self) -> pandas.DataFrame:
        result = self._modin_frame.to_pandas()
        if BenchmarkMode.get():
            pass  # to_pandas is inherently synchronous
        return result

    def to_numpy(self, **kwargs: Any) -> np.ndarray:
        return self._modin_frame.to_numpy(**kwargs)

    def to_interchange_dataframe(self, nan_as_null: bool = False, allow_copy: bool = True):
        """Native-buffer protocol producer: per-column, zero-copy over
        host caches, one device fetch per requested computed column — no
        intermediate pandas frame (ref: pandas/interchange/, 2,228 LoC)."""
        from modin_tpu.core.dataframe.tpu.interchange.dataframe import (
            TpuDataFrameXchg,
        )

        return TpuDataFrameXchg(
            self._modin_frame, nan_as_null=nan_as_null, allow_copy=allow_copy
        )

    def copy(self) -> "TpuQueryCompiler":
        if self._plan is not None:
            # plans are immutable; a copy shares the pending plan
            return type(self).from_plan(self._plan, self._shape_hint)
        return type(self)(self._modin_frame.copy(), self._shape_hint)

    def free(self) -> None:
        if self._plan is not None:
            # drop the plan: a Source leaf (Force mode / defer_frame) holds
            # an eager snapshot sharing the original frame's live buffers —
            # those must not be freed here, only dereferenced — and scan-
            # level lowered-read caches release with the node graph
            self._plan = None
            return
        self._modin_frame.free()

    def finalize(self) -> None:
        self._modin_frame.finalize()

    def execute(self) -> None:
        self._modin_frame.finalize()

    def dispatch(self) -> None:
        """Dispatch all deferred device work WITHOUT a host block.

        The async counterpart of ``execute``: callers that have their own
        completion barrier (e.g. the bench's FIFO token fetch — a
        ``block_until_ready`` is one more host sync) use this to put
        the work on the stream and nothing more."""
        self._modin_frame.materialize_device()

    # ------------------------------------------------------------------ #
    # Metadata
    # ------------------------------------------------------------------ #

    def get_index(self) -> pandas.Index:
        return self._modin_frame.index

    def get_columns(self) -> pandas.Index:
        if self._plan is not None:
            return graftplan.plan_columns(self)
        return self._modin_frame.columns

    def _set_index(self, value: Any) -> None:
        self._modin_frame = self._modin_frame.copy()
        self._modin_frame.index = value

    def _set_columns(self, value: Any) -> None:
        self._modin_frame = self._modin_frame.copy()
        self._modin_frame.columns = value

    index = property(get_index, _set_index)
    columns = property(get_columns, _set_columns)

    @property
    def dtypes(self) -> pandas.Series:
        if self._plan is not None:
            known = graftplan.plan_dtypes(self)
            if known is not None:
                return known
        return self._modin_frame.dtypes

    def get_axis_len(self, axis: int) -> int:
        if axis and self._plan is not None:
            return len(graftplan.plan_columns(self))
        return self._modin_frame.num_cols if axis else len(self._modin_frame)

    # ------------------------------------------------------------------ #
    # Backend cost model: large frames want to stay on device
    # ------------------------------------------------------------------ #

    def stay_cost(self, api_cls_name, operation, arguments) -> Optional[int]:
        if operation:
            import inspect

            own = getattr(type(self), operation, None)
            base = getattr(BaseQueryCompiler, operation, None)
            own_fn = inspect.unwrap(own) if own is not None else None
            base_fn = inspect.unwrap(base) if base is not None else None
            if (
                own_fn is not None
                and own_fn is base_fn
                and len(self._modin_frame) <= 1_000_000
            ):
                # no device kernel for this op: it will round-trip through
                # host pandas anyway, so a small frame is cheaper off-device
                return QCCoercionCost.COST_MEDIUM
        return QCCoercionCost.COST_ZERO

    def move_to_cost(self, other_qc_type, api_cls_name, operation, arguments) -> Optional[int]:
        if type(self) is other_qc_type:
            return QCCoercionCost.COST_ZERO
        # transfer-size aware: the PCIe cost of leaving the device
        # scales with the frame, so a mid-size device frame outprices a
        # small host frame's move in the calculator regardless of which
        # operand is self
        nrows = len(self._modin_frame)
        if nrows > 10_000_000:
            return QCCoercionCost.COST_HIGH
        if nrows > 64_000:
            return QCCoercionCost.COST_MEDIUM
        return QCCoercionCost.COST_LOW

    # ------------------------------------------------------------------ #
    # Structural fast paths (host metadata + device gather)
    # ------------------------------------------------------------------ #

    def getitem_column_array(self, key: Any, numeric: bool = False, ignore_order: bool = False) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_project(self, key, numeric)
            if planned is not None:
                return planned
        frame = self._modin_frame
        if numeric:
            positions = [int(k) for k in key]
        else:
            positions = []
            indexer = frame.columns.get_indexer_for(list(key))
            if (np.asarray(indexer) == -1).any():
                return super().getitem_column_array(key, numeric=numeric)
            positions = [int(i) for i in indexer]
        return type(self)(frame.select_columns_by_position(positions))

    def getitem_row_array(self, key: Any) -> "TpuQueryCompiler":
        return type(self)(
            self._modin_frame.take_rows_positional(np.asarray(list(key), dtype=np.int64)),
            self._shape_hint,
        )

    def row_slice(self, start: Optional[int], stop: Optional[int], step: Optional[int] = None) -> "TpuQueryCompiler":
        return type(self)(
            self._modin_frame.take_rows_positional(slice(start, stop, step)),
            self._shape_hint,
        )

    def take_2d_positional(self, index: Any = None, columns: Any = None) -> "TpuQueryCompiler":
        frame = self._modin_frame
        if columns is not None:
            if isinstance(columns, slice):
                positions = list(range(*columns.indices(frame.num_cols)))
            else:
                positions = [int(c) for c in columns]
            frame = frame.select_columns_by_position(positions)
        if index is not None:
            if not isinstance(index, slice):
                # materialize generators; arrays/Index pass through without
                # the million-python-int list a bare list() would build
                if not hasattr(index, "__len__"):
                    index = list(index)
                index = np.asarray(index, dtype=np.int64)
            frame = frame.take_rows_positional(index)
        return type(self)(frame)

    def getitem_array(self, key: Any) -> "TpuQueryCompiler":
        if (
            (self._plan is not None or graftplan.FORCE_ON)
            and isinstance(key, TpuQueryCompiler)
        ):
            planned = graftplan.defer_filter(self, key)
            if planned is not None:
                return planned
        if isinstance(key, TpuQueryCompiler):
            mask_frame = key._modin_frame
            if (
                mask_frame.num_cols == 1
                and mask_frame.get_column(0).is_device
                and len(mask_frame) == len(self._modin_frame)
                # pandas aligns a boolean-Series mask to the frame's index;
                # the positional fast path is only valid when the indexes
                # already match (ref: pandas check_bool_indexer).
                and self._fast_index_match(key)
            ):
                mcol = mask_frame.get_column(0)
                if mcol.pandas_dtype == np.dtype(bool):
                    frame = self._modin_frame
                    cached = mcol.host_cache is not None and all(
                        (not c.is_device) or c.host_cache is not None
                        for c in frame._columns
                    )
                    if cached:
                        # everything already has bit-exact host copies: the
                        # host-positions path is free and keeps the caches
                        return type(self)(
                            frame.filter_rows_mask(mcol.to_numpy())
                        )
                    # computed data: compact on device — the (possibly
                    # deferred) mask fuses into the kernel; one scalar sync
                    return type(self)(frame.filter_rows_mask_device(mcol.raw))
            return super().getitem_array(key)
        key_arr = np.asarray(key)
        if key_arr.dtype == bool:
            if len(key_arr) != len(self._modin_frame):
                raise ValueError(
                    f"Item wrong length {len(key_arr)} instead of "
                    f"{len(self._modin_frame)}."
                )
            return type(self)(self._modin_frame.filter_rows_mask(key_arr))
        return super().getitem_array(key)

    def _column_from_value(self, value: Any) -> Optional[Any]:
        """Build a column for setitem/insert from a compatible value, or None."""
        import jax.numpy as jnp

        from modin_tpu.ops.structural import pad_len

        frame = self._modin_frame
        n = len(frame)
        if isinstance(value, TpuQueryCompiler):
            vframe = value._modin_frame
            if (
                vframe.num_cols == 1
                and len(vframe) == n
                and self._fast_index_match(value)
            ):
                return vframe.get_column(0)
            return None
        if isinstance(value, (int, float, np.integer, np.floating)) and not isinstance(value, bool):
            data = jnp.full(pad_len(n), value)
            return DeviceColumn(data, np.dtype(data.dtype), length=n)
        if isinstance(value, (bool, np.bool_)):
            return DeviceColumn(
                jnp.full(pad_len(n), bool(value)), np.dtype(bool), length=n
            )
        if isinstance(value, (np.ndarray, list, tuple, range)):
            arr = np.asarray(value)
            if arr.ndim == 1 and len(arr) == n and arr.dtype.kind in "biufmM":
                return DeviceColumn.from_numpy(arr)
            if arr.ndim == 1 and len(arr) == n:
                return HostColumn(pandas.array(arr))
        return None

    def rowwise_query(self, expr: str, **kwargs: Any) -> "TpuQueryCompiler":
        """Row-wise ``df.query`` compiled onto the device operator surface
        (reference pandas/query_compiler.py:3585 — NotImplementedError routes
        the caller to the pandas fallback)."""
        local_dict = kwargs.pop("local_dict", None)
        if kwargs:
            raise NotImplementedError(
                "only plain row-wise expressions take the native query path"
            )
        from modin_tpu.core.computation.eval import try_query
        from modin_tpu.pandas.dataframe import DataFrame

        result = try_query(DataFrame(query_compiler=self), expr, local_dict)
        if result is None:
            raise NotImplementedError(
                f"the expression {expr!r} is not a supported row-wise query"
            )
        return result._query_compiler

    def setitem(self, axis: int, key: Any, value: Any) -> "TpuQueryCompiler":
        if axis == 0:
            frame = self._modin_frame
            col = self._column_from_value(value)
            if col is not None and len(frame) > 0:
                positions = (
                    [int(p) for p in frame.columns.get_indexer_for([key])]
                    if key in frame.columns
                    else []
                )
                new_cols = list(frame._columns)
                if len(positions) == 1 and positions[0] >= 0:
                    new_cols[positions[0]] = col
                    return type(self)(frame.with_columns(new_cols))
                if not positions:
                    new_cols.append(col)
                    new_labels = frame.columns.append(pandas.Index([key]))
                    return type(self)(frame.with_columns(new_cols, new_labels))
        return super().setitem(axis, key, value)

    def insert(self, loc: int, column: Any, value: Any) -> "TpuQueryCompiler":
        frame = self._modin_frame
        col = self._column_from_value(value)
        if col is not None and len(frame) > 0:
            new_cols = list(frame._columns)
            new_cols.insert(loc, col)
            new_labels = frame.columns.insert(loc, column)
            return type(self)(frame.with_columns(new_cols, new_labels))
        return super().insert(loc, column, value)

    def drop(self, index: Any = None, columns: Any = None, errors: str = "raise") -> "TpuQueryCompiler":
        result = self
        frame = self._modin_frame
        if columns is not None:
            cols_list = [columns] if isinstance(columns, (str, int, tuple)) or not hasattr(columns, "__iter__") else list(columns)
            keep = [
                i for i, label in enumerate(frame.columns)
                if label not in set(cols_list)
            ]
            frame = frame.select_columns_by_position(keep)
            result = type(self)(frame)
        if index is not None:
            idx_list = list(index) if hasattr(index, "__iter__") and not isinstance(index, (str, tuple)) else [index]
            current = frame.index
            mask = ~current.isin(idx_list)
            frame = frame.filter_rows_mask(np.asarray(mask))
            result = type(self)(frame)
        return result

    def concat(self, axis: int, other: Any, join: str = "outer", ignore_index: bool = False, sort: bool = False, **kwargs: Any) -> "TpuQueryCompiler":
        if not isinstance(other, (list, tuple)):
            other = [other]
        if axis == 0 and all(isinstance(o, TpuQueryCompiler) for o in other):
            frames = [o._modin_frame for o in other]
            base = self._modin_frame
            if all(
                f.columns.equals(base.columns)
                and list(f.dtypes) == list(base.dtypes)
                for f in frames
            ):
                result = base.concat_rows(frames)
                qc = type(self)(result)
                if ignore_index:
                    qc._modin_frame._index = LazyIndex(
                        pandas.RangeIndex(len(result)), len(result)
                    )
                return qc
        if (
            axis == 1
            and not ignore_index
            and not sort  # sort=True reorders even identical indexes
            and all(isinstance(o, TpuQueryCompiler) for o in other)
            and all(self._fast_index_match(o) for o in other)
        ):
            # column concat of index-aligned frames: append the column lists,
            # zero data movement (census: the get_dummies-then-concat
            # pattern).  Duplicate labels are legal in pandas concat.
            base = self._modin_frame
            new_cols = list(base._columns)
            labels = list(base.columns)
            for o in other:
                of = o._modin_frame
                new_cols.extend(of._columns)
                labels.extend(of.columns)
            try:
                label_index = pandas.Index(labels)
            except (TypeError, ValueError):
                # mixed unorderable label types: pandas' own concat figures
                # out the result index; device failures can't occur here
                return super().concat(
                    axis, other, join=join, ignore_index=ignore_index,
                    sort=sort, **kwargs
                )
            return type(self)(
                TpuDataframe(new_cols, label_index, base._index, nrows=len(base))
            )
        return super().concat(axis, other, join=join, ignore_index=ignore_index, sort=sort, **kwargs)

    def columnarize(self) -> "TpuQueryCompiler":
        if self._plan is not None and len(self.get_columns()) == 1:
            # reduce results (the 1-row unnamed-series transpose case) are
            # always materialized, so a pending single-column plan only needs
            # the Series tag
            result = self.copy()
            result._shape_hint = "column"
            return result
        result = super().columnarize()
        return result

    def repartition(self, axis: Any = None) -> "TpuQueryCompiler":
        return self

    def get_pandas_backend(self) -> Optional[str]:
        return None

    # ================================================================== #
    # Device hot paths.  Each op gates on dtypes/kwargs it can honor on
    # device and falls through to the inherited default otherwise —
    # the reference's incremental-optimization strategy.
    # ================================================================== #

    _ARITH_KINDS = frozenset("iuf")
    _LOGICAL_OPS = frozenset(
        ["__and__", "__or__", "__xor__", "__rand__", "__ror__", "__rxor__"]
    )
    _CMP_OPS = frozenset(["eq", "ne", "lt", "le", "gt", "ge"])

    def _device_cols(self) -> Optional[list]:
        """All columns as concrete device arrays (batch-materializing any
        deferred expressions in one jit), or None if any column is host-only."""
        cols = self._modin_frame._columns
        if all(c.is_device and not c.is_category for c in cols):
            self._modin_frame.materialize_device()
            return [c.data for c in cols]
        return None

    def _device_raw(self) -> Optional[list]:
        """All columns as device arrays OR deferred expressions — the
        fusion-aware variant of _device_cols for elementwise/reduction paths
        that extend the lazy chain instead of forcing it."""
        cols = self._modin_frame._columns
        if all(c.is_device and not c.is_category for c in cols):
            return [c.raw for c in cols]
        return None

    def _fast_index_match(self, other: "TpuQueryCompiler") -> bool:
        """Cheap index-alignment check that never materializes a lazy index."""
        a, b = self._modin_frame._index, other._modin_frame._index
        if a is b:
            return True
        if a.is_materialized and b.is_materialized:
            ia, ib = a.get(), b.get()
            if ia is ib:
                return True
            if isinstance(ia, pandas.RangeIndex) and isinstance(ib, pandas.RangeIndex):
                return ia.equals(ib)
            if len(ia) == len(ib) and len(ia) <= 100_000:
                return ia.equals(ib)
        return False

    def _wrap_device_result(
        self,
        datas: list,
        dtypes: Optional[list] = None,
        col_labels: Optional[pandas.Index] = None,
        index: Any = None,
        nrows: Optional[int] = None,
    ) -> "TpuQueryCompiler":
        frame = self._modin_frame
        length = nrows if nrows is not None else len(frame)
        cols = [
            DeviceColumn(
                d,
                np.dtype(dt) if dt is not None else np.dtype(d.dtype),
                length=length,
            )
            for d, dt in zip(datas, dtypes or [None] * len(datas))
        ]
        return type(self)(
            frame.with_columns(
                cols,
                col_labels if col_labels is not None else frame.columns,
                index if index is not None else frame._index,
                nrows=nrows,
            ),
            self._shape_hint,
        )

    # ------------------------------- binary --------------------------- #

    @device_path("binary")
    def _try_dict_compare(self, op: str, other: str) -> Optional["TpuQueryCompiler"]:
        """String-scalar comparisons on dictionary-encoded columns: sorted
        categories turn every comparison into a CODE-threshold test (one
        searchsorted on the tiny category array host-side, one device
        compare).  pandas semantics verified: missing rows are False for
        eq/lt/le/gt/ge and True for ne."""
        import jax.numpy as jnp

        from modin_tpu.ops.dictionary import encode_host_column

        frame = self._modin_frame
        datas = []
        for c in frame._columns:
            if c.is_device or isinstance(c.pandas_dtype, pandas.CategoricalDtype):
                return None
            if (
                isinstance(c.pandas_dtype, pandas.StringDtype)
                and c.pandas_dtype.na_value is pandas.NA
            ):
                # NA-backed 'string' comparisons yield a boolean EXTENSION
                # dtype with NA propagation — keep the pandas fallback
                return None
            enc = encode_host_column(c)
            if enc is None:
                return None
            try:
                pos = int(np.searchsorted(enc.categories, other))
            except TypeError:
                return None
            exact = bool(
                pos < len(enc.categories) and enc.categories[pos] == other
            )
            codes = enc.codes.data
            if op in ("eq", "ne"):
                eqmask = (
                    codes == float(pos)
                    if exact
                    else jnp.zeros(codes.shape, bool)
                )
                # NaN codes compare unequal -> ne True, matching pandas
                out = eqmask if op == "eq" else ~eqmask
            elif op == "lt":
                out = codes < float(pos)
            elif op == "le":
                out = codes < float(pos + (1 if exact else 0))
            elif op == "gt":
                out = codes >= float(pos + (1 if exact else 0))
            elif op == "ge":
                out = codes >= float(pos)
            else:
                return None
            datas.append(out)
        return self._wrap_device_result(
            datas, dtypes=[np.dtype(bool)] * len(datas)
        )

    @device_path("binary")
    def _try_device_binary(self, op: str, other: Any, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        from modin_tpu.ops import elementwise

        if kwargs.get("level") is not None or kwargs.get("fill_value") is not None:
            return None
        frame = self._modin_frame
        if frame.num_cols == 0 or len(frame) == 0:
            return None
        if op in self._CMP_OPS and isinstance(other, str):
            result = self._try_dict_compare(op, other)
            if result is not None:
                return result
        cols = self._device_raw()
        if cols is None:
            return None
        kinds = [c.pandas_dtype.kind for c in frame._columns]
        if op in self._LOGICAL_OPS:
            if not all(k == "b" for k in kinds):
                return None
        elif op in self._CMP_OPS:
            if not all(k in "biuf" for k in kinds):
                return None
        else:
            if not all(k in self._ARITH_KINDS for k in kinds):
                return None

        # scalar other
        if isinstance(other, (int, float, np.integer, np.floating)) and not isinstance(other, bool):
            if (
                op in ("pow", "rpow")
                and all(k in "iu" for k in kinds)
                and isinstance(other, (int, np.integer))
            ):
                # int ** negative-int raises in pandas; rpow exponent sign is
                # data-dependent — fall back for the whole int/int pow family
                return None
            if all(k in "iub" for k in kinds) and isinstance(other, (int, np.integer)):
                # pandas 3 promotes int floordiv/mod to float64 (inf/nan)
                # when any divisor is zero — data-dependent result dtype
                if op in ("floordiv", "mod") and int(other) == 0:
                    return None
                if op in ("rfloordiv", "rmod"):
                    return None  # the divisor is the (data) column
            datas = elementwise.binary_op_columns(op, cols, other)
            return self._wrap_device_result(datas)
        if isinstance(other, (bool, np.bool_)) and op in (self._LOGICAL_OPS | self._CMP_OPS):
            datas = elementwise.binary_op_columns(op, cols, bool(other))
            return self._wrap_device_result(datas)

        # frame/series other
        if isinstance(other, TpuQueryCompiler):
            oframe = other._modin_frame
            ocols = other._device_raw()
            if ocols is None or not self._fast_index_match(other):
                return None
            okinds = [c.pandas_dtype.kind for c in oframe._columns]
            if op in self._LOGICAL_OPS:
                if not all(k == "b" for k in okinds):
                    return None
            elif not all(k in "biuf" for k in okinds):
                return None
            if (
                op in ("pow", "rpow")
                and all(k in "iu" for k in kinds)
                and all(k in "iu" for k in okinds)
            ):
                return None  # exponent sign is data-dependent; pandas may raise
            if (
                op in ("floordiv", "rfloordiv", "mod", "rmod")
                and all(k in "iub" for k in kinds)
                and all(k in "iub" for k in okinds)
            ):
                # pandas 3: any zero divisor promotes the int result to
                # float64 (inf/nan) — data-dependent dtype, so fall back
                return None
            axis = kwargs.get("axis", None)
            self_is_col = self._shape_hint == "column"
            other_is_col = other._shape_hint == "column"
            if self_is_col and other_is_col:
                # series <op> series
                datas = elementwise.binary_op_columns(op, cols, ocols)
                a, b = frame.columns[0], oframe.columns[0]
                label = a if a == b else MODIN_UNNAMED_SERIES_LABEL
                return self._wrap_device_result(datas, col_labels=pandas.Index([label]))
            if not self_is_col and other_is_col and axis in (0, "index"):
                # df <op> series broadcast down columns
                datas = elementwise.binary_op_columns(op, cols, ocols * frame.num_cols)
                return self._wrap_device_result(datas)
            if not self_is_col and not other_is_col:
                if not frame.columns.equals(oframe.columns):
                    return None
                datas = elementwise.binary_op_columns(op, cols, ocols)
                return self._wrap_device_result(datas)
            return None
        return None

    # ------------------------------- maps ----------------------------- #

    def _map_device_host(
        self,
        device_fn,
        host_fn,
        result_dtype_fn=None,
        require_kinds: Optional[str] = None,
    ) -> Optional["TpuQueryCompiler"]:
        """Apply a kernel to device columns and a pandas kernel to host
        columns, preserving column positions (the hybrid device/host map)."""
        from modin_tpu.ops import elementwise  # noqa: F401

        frame = self._modin_frame
        if len(frame) == 0:
            return None
        device_positions = []
        device_arrays = []
        for i, col in enumerate(frame._columns):
            if col.is_category:
                return None  # codes are not values: the pandas default answers
            if col.is_device:
                if require_kinds is not None and col.pandas_dtype.kind not in require_kinds:
                    return None
                device_positions.append(i)
                device_arrays.append(col.raw)
        new_device = device_fn(device_arrays) if device_arrays else []
        new_columns: list = list(frame._columns)
        for pos, data in zip(device_positions, new_device):
            old = frame._columns[pos]
            keep_logical = data.dtype == old.raw.dtype
            new_columns[pos] = DeviceColumn(
                data,
                old.pandas_dtype if keep_logical else np.dtype(data.dtype),
                length=len(frame),
            )
        for i, col in enumerate(frame._columns):
            if not col.is_device:
                result = host_fn(pandas.Series(col.data))
                new_columns[i] = HostColumn(result.array)
        return type(self)(
            frame.with_columns(new_columns), self._shape_hint
        )

    _MATH_UNARY = frozenset(
        ["sqrt", "exp", "log", "log2", "log10", "sin", "cos", "tan", "tanh",
         "floor", "ceil", "sign"]
    )

    def unary_math(self, op_name: str) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_unary(self, "unary_math", (op_name,))
            if planned is not None:
                return planned
        from modin_tpu.ops import elementwise

        if op_name in self._MATH_UNARY:
            result = self._map_device_host(
                lambda cols: elementwise.unary_op_columns(op_name, cols),
                lambda s: pandas.Series(
                    getattr(np, op_name)(s.to_numpy()), index=s.index
                ),
                require_kinds="iuf",
            )
            if result is not None:
                return result
        return super().unary_math(op_name)

    def abs(self) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_unary(self, "abs")
            if planned is not None:
                return planned
        from modin_tpu.ops import elementwise

        result = self._map_device_host(
            lambda cols: elementwise.unary_op_columns("abs", cols),
            lambda s: s.abs(),
            require_kinds="iuf",
        )
        return result if result is not None else super().abs()

    def negative(self) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_unary(self, "negative")
            if planned is not None:
                return planned
        from modin_tpu.ops import elementwise

        result = self._map_device_host(
            lambda cols: elementwise.unary_op_columns("negative", cols),
            lambda s: -s,
            require_kinds="iuf",
        )
        return result if result is not None else super().negative()

    def invert(self) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_unary(self, "invert")
            if planned is not None:
                return planned
        from modin_tpu.ops import elementwise

        result = self._map_device_host(
            lambda cols: elementwise.unary_op_columns("invert", cols),
            lambda s: ~s,
            require_kinds="biu",
        )
        return result if result is not None else super().invert()

    def _isna_like(self, negate: bool) -> Optional["TpuQueryCompiler"]:
        from modin_tpu.ops import elementwise

        frame = self._modin_frame
        device_positions = [
            i for i, c in enumerate(frame._columns) if c.is_device
        ]
        mM_flags = tuple(
            frame._columns[i].pandas_dtype.kind in "mM" for i in device_positions
        )

        def device_fn(cols):
            return elementwise.isna_columns(cols, mM_flags, negate)

        return self._map_device_host(
            device_fn,
            (lambda s: s.notna()) if negate else (lambda s: s.isna()),
        )

    def isna(self) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_unary(self, "isna", bool_out=True)
            if planned is not None:
                return planned
        result = self._isna_like(negate=False)
        return result if result is not None else super().isna()

    def notna(self) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_unary(self, "notna", bool_out=True)
            if planned is not None:
                return planned
        result = self._isna_like(negate=True)
        return result if result is not None else super().notna()

    def round(self, decimals: int = 0, **kwargs: Any) -> "TpuQueryCompiler":
        if (self._plan is not None or graftplan.FORCE_ON) and isinstance(
            decimals, int
        ):
            planned = graftplan.defer_unary(
                self, "round", (), dict(decimals=decimals, **kwargs)
            )
            if planned is not None:
                return planned
        from modin_tpu.ops import elementwise

        if not isinstance(decimals, (int, np.integer)):
            return super().round(decimals=decimals, **kwargs)
        result = self._map_device_host(
            lambda cols: elementwise.round_columns(cols, int(decimals)),
            lambda s: s.round(int(decimals)) if s.dtype.kind in "iuf" else s,
        )
        return result if result is not None else super().round(decimals=decimals, **kwargs)

    def fillna(self, **kwargs: Any) -> "TpuQueryCompiler":
        from modin_tpu.ops import elementwise

        value = kwargs.get("value")
        if (
            isinstance(value, (int, float, np.integer, np.floating))
            and not isinstance(value, bool)
            and kwargs.get("limit") is None
            and kwargs.get("axis") in (0, None)
        ):
            # note: pandas upcasts int fill into float col fine; int cols have
            # no NaN so they pass through unchanged.  Datetime columns are
            # excluded: pandas coerces them to object when filled with a number
            result = self._map_device_host(
                lambda cols: elementwise.fillna_columns(cols, value),
                lambda s: s.fillna(value),
                require_kinds="biuf",
            )
            if result is not None:
                return result
        # per-column scalar mapping: fillna(dict) / fillna(df.mean()) — each
        # mapped numeric column fills on device, unmapped columns pass
        # through untouched (census: the all_data.fillna(all_data.mean())
        # Kaggle pattern)
        mapping = None
        if isinstance(value, dict):
            mapping = value
        elif isinstance(value, BaseQueryCompiler) and kwargs.get("squeeze_value"):
            ser = value.to_pandas()
            ser = ser.iloc[:, 0] if ser.shape[1] == 1 else None
            if ser is not None and ser.index.is_unique:
                mapping = ser.to_dict()
        if (
            mapping is not None
            and kwargs.get("limit") is None
            and kwargs.get("axis") in (0, None)
            and not kwargs.get("squeeze_self")
            and all(
                isinstance(v, (int, float, np.integer, np.floating))
                and not isinstance(v, bool)
                for v in mapping.values()
            )
        ):
            frame = self._modin_frame
            ok = len(frame) > 0
            if ok:
                for i, label in enumerate(frame.columns):
                    if label not in mapping:
                        continue
                    c = frame._columns[i]
                    if not (c.is_device and c.pandas_dtype.kind in "biuf"):
                        ok = False
                        break
            if ok:
                import jax.numpy as jnp

                frame.materialize_device()
                new_cols = list(frame._columns)
                for i, label in enumerate(frame.columns):
                    if label not in mapping:
                        continue
                    c = frame._columns[i]
                    if c.pandas_dtype.kind != "f":
                        continue  # int/bool columns carry no NaN
                    fillv = mapping[label]
                    if isinstance(fillv, float) and np.isnan(fillv):
                        continue  # NaN fill is a no-op
                    data = jnp.where(
                        jnp.isnan(c.data),
                        jnp.asarray(fillv, c.data.dtype),
                        c.data,
                    )
                    new_cols[i] = DeviceColumn(
                        data, c.pandas_dtype, length=len(frame)
                    )
                return type(self)(
                    TpuDataframe(
                        new_cols, frame._col_labels, frame._index,
                        nrows=len(frame),
                    )
                )
        return super().fillna(**kwargs)

    def clip(self, lower: Any, upper: Any, **kwargs: Any) -> "TpuQueryCompiler":
        from modin_tpu.ops import elementwise

        def is_num(v):
            return v is None or (
                isinstance(v, (int, float, np.integer, np.floating))
                and not isinstance(v, bool)
            )

        if is_num(lower) and is_num(upper) and kwargs.get("axis") in (None, 0) and not kwargs.get("inplace"):
            result = self._map_device_host(
                lambda cols: elementwise.clip_columns(cols, lower, upper),
                lambda s: s.clip(lower, upper),
                require_kinds="iuf",
            )
            if result is not None:
                return result
        return super().clip(lower, upper, **kwargs)

    def astype(self, col_dtypes: Any, errors: str = "raise") -> "TpuQueryCompiler":
        from modin_tpu.ops import elementwise

        frame = self._modin_frame
        if not isinstance(col_dtypes, dict):
            try:
                target = np.dtype(col_dtypes)
            except TypeError:
                return super().astype(col_dtypes, errors=errors)
            if target.kind in "iuf" and all(
                c.is_device and c.pandas_dtype.kind in "biuf"
                for c in frame._columns
            ) and len(frame) > 0:
                # int target with NaN present must raise like pandas
                if target.kind in "iu" and any(
                    c.pandas_dtype.kind == "f" for c in frame._columns
                ):
                    return super().astype(col_dtypes, errors=errors)
                new_cols = [
                    DeviceColumn(
                        elementwise.astype_column(c.data, target), target,
                        length=len(frame),
                    )
                    for c in frame._columns
                ]
                return type(self)(frame.with_columns(new_cols), self._shape_hint)
        return super().astype(col_dtypes, errors=errors)

    def _cum_op(self, name: str, axis: int, skipna: bool) -> Optional["TpuQueryCompiler"]:
        from modin_tpu.ops import elementwise

        if axis != 0:
            return None
        frame = self._modin_frame
        kinds = [c.pandas_dtype.kind for c in frame._columns]
        # floats use the NaN-skipping kernels (skipna=True only); ints exact
        if not all(c.is_device for c in frame._columns) or len(frame) == 0:
            return None
        if not all(k in "iuf" for k in kinds):
            return None
        if not skipna and any(k == "f" for k in kinds):
            return None  # NaN-propagating variant not implemented on device
        return self._map_device_host(
            lambda cols: elementwise.unary_op_columns(name, cols),
            lambda s: s,
        )

    def cumsum(self, axis: int = 0, skipna: bool = True, **kwargs: Any) -> "TpuQueryCompiler":
        result = self._cum_op("cumsum", axis, skipna)
        return result if result is not None else super().cumsum(axis=axis, skipna=skipna, **kwargs)

    def cumprod(self, axis: int = 0, skipna: bool = True, **kwargs: Any) -> "TpuQueryCompiler":
        result = self._cum_op("cumprod", axis, skipna)
        return result if result is not None else super().cumprod(axis=axis, skipna=skipna, **kwargs)

    def cummax(self, axis: int = 0, skipna: bool = True, **kwargs: Any) -> "TpuQueryCompiler":
        result = self._cum_op("cummax", axis, skipna)
        return result if result is not None else super().cummax(axis=axis, skipna=skipna, **kwargs)

    def cummin(self, axis: int = 0, skipna: bool = True, **kwargs: Any) -> "TpuQueryCompiler":
        result = self._cum_op("cummin", axis, skipna)
        return result if result is not None else super().cummin(axis=axis, skipna=skipna, **kwargs)

    # ----------------------------- reductions ------------------------- #

    _DEVICE_REDUCTIONS = frozenset(
        ["sum", "prod", "mean", "median", "min", "max", "count", "var", "std",
         "sem", "skew", "kurt", "any", "all"]
    )

    @device_path("reduce")
    def _try_device_reduce(
        self, op: str, axis: Any, skipna: bool, numeric_only: bool, kwargs: dict,
        keep: Any = None, donate_cols: Any = None,
    ) -> Optional["TpuQueryCompiler"]:
        """``keep``/``donate_cols`` are the graftfuse whole-plan leg
        (plan/fuse.py): ``keep`` is a deferred boolean mask over the
        UNCOMPACTED rows — the filter fuses into the reduction program
        instead of paying a compaction dispatch — and ``donate_cols`` are
        input columns whose buffers the ledger proved donation-safe.
        ``keep`` declines (returns None) wherever the masked form is not
        bit-faithful to the staged one: axis=1, the sort-shaped median
        leg, dictionary-encoded host columns, and a filter that keeps zero
        rows (pandas empty-frame semantics live with the staged path)."""
        from modin_tpu.ops import reductions

        if kwargs.get("min_count", 0) not in (0, -1):
            return None
        if kwargs.get("bool_only"):
            return None
        ddof = int(kwargs.get("ddof", 1))
        frame = self._modin_frame
        if len(frame) == 0 or frame.num_cols == 0:
            return None
        # column selection
        allowed = "biuf"
        # string/object columns join min/max/count through their dictionary
        # codes (sorted categories: code min/max IS the lexicographic one);
        # decoders[i] carries the categories for result translation
        dict_ok = op in ("min", "max", "count") and axis in (0, None)
        positions = []
        decoders: dict = {}
        for i, col in enumerate(frame._columns):
            ok = col.is_device and col.pandas_dtype.kind in allowed
            if numeric_only:
                if ok:
                    positions.append(i)
                elif col.pandas_dtype.kind not in "biufc":
                    continue  # excluded by numeric_only
                else:
                    return None  # numeric column we can't run on device
            else:
                if not ok:
                    if dict_ok and not col.is_device and not isinstance(
                        col.pandas_dtype, pandas.CategoricalDtype
                    ):
                        from modin_tpu.ops.dictionary import encode_host_column

                        enc = encode_host_column(col)
                        # empty categories = all-missing column; pandas'
                        # reduction quirks there (None vs nan) stay with it
                        if enc is not None and len(enc.categories):
                            decoders[i] = enc
                            positions.append(i)
                            continue
                    return None
                positions.append(i)
        if not positions:
            return None
        sel_cols = [
            frame._columns[i] if i not in decoders else decoders[i].codes
            for i in positions
        ]
        if keep is not None and (decoders or axis in (1,)):
            return None
        labels = frame.columns[positions]
        # raw: lazy elementwise producers fuse into the reduction tail
        arrays = [c.raw for c in sel_cols]
        # bool columns: pandas computes sum/mean over ints (cast in-fusion)
        cast_bool = op in ("sum", "prod", "mean", "median", "var", "std", "sem", "skew", "kurt")
        if axis in (1,):
            if op not in ("sum", "mean", "min", "max", "count", "var", "std", "median"):
                return None
            data = reductions.reduce_axis1(
                op, arrays, skipna=skipna, ddof=ddof, cast_bool=cast_bool
            )
            result_col = DeviceColumn(data, np.dtype(data.dtype), length=len(frame))
            result_frame = TpuDataframe(
                [result_col],
                pandas.Index([MODIN_UNNAMED_SERIES_LABEL]),
                frame._index,
            )
            qc = type(self)(result_frame)
            qc._shape_hint = "column"
            return qc
        if axis not in (0, None):
            return None
        if keep is not None:
            if op == "median":
                return None  # masked median has no fused form
            values, kept = reductions.reduce_columns_masked(
                op, arrays, keep, len(frame), skipna=skipna, ddof=ddof,
                cast_bool=cast_bool, donate_cols=donate_cols,
            )
            if kept == 0:
                # a filter matching nothing at fused scale pays one
                # discarded dispatch here (donated inputs restore
                # transparently from host on the staged re-run): pandas
                # empty-frame semantics — int min answering NaN, var
                # edges — are not worth expressing in-program for a query
                # that selected zero rows
                return None
        elif (
            op == "median"
            and not decoders
            and all(not c.is_lazy for c in sel_cols)
        ):
            # graftsort: concrete columns take the shared-sorted-
            # representation median (one sort amortized across the whole
            # sort-shaped family, correct skipna=False semantics),
            # router-gated; lazy chains keep the fused nanmedian tail.
            # graftview: a cached whole-result artifact answers without any
            # device work and flips the router crossover ("view" strategy)
            from modin_tpu.ops import sorted_cache
            from modin_tpu.ops.router import decide

            from modin_tpu.views import reduce_cache as view_reduce

            med_params = (bool(skipna),)
            cached_med: dict = {}
            if graftview.VIEWS_ON:
                cached_med = view_reduce.sort_reduce_lookup(
                    "median", med_params, sel_cols
                )
            strategies = [
                "view" if i in cached_med
                else ("cached" if sorted_cache.peek(c) else "sort")
                for i, c in enumerate(sel_cols)
            ]
            if decide("median", len(frame), strategies) == "host":
                return None
            view_reduce.sort_reduce_consume(
                "median", med_params, sel_cols, cached_med
            )
            values = [None] * len(sel_cols)
            miss_is = [i for i in range(len(sel_cols)) if i not in cached_med]
            if miss_is:
                got = reductions.median_columns(
                    [sel_cols[i] for i in miss_is], len(frame), skipna=skipna
                )
                for i, v in zip(miss_is, got):
                    values[i] = v
                    if graftview.VIEWS_ON:
                        view_reduce.sort_reduce_store(
                            "median", med_params, sel_cols[i], v
                        )
            for i, v in cached_med.items():
                values[i] = v
        else:
            values = None
            if graftview.VIEWS_ON and not donate_cols:
                from modin_tpu.views.reduce_cache import cached_reduce

                values = cached_reduce(
                    op, sel_cols, len(frame), skipna, ddof, cast_bool
                )
            if values is None:
                values = reductions.reduce_columns(
                    op, arrays, len(frame), skipna=skipna, ddof=ddof,
                    cast_bool=cast_bool, donate_cols=donate_cols,
                )
        out_values = []
        for pos, v in zip(positions, values):
            v = v.item() if v.ndim == 0 else v
            if pos in decoders and op in ("min", "max"):
                from modin_tpu.ops.dictionary import decode_codes

                v = decode_codes(
                    np.asarray([v], np.float64), decoders[pos].categories
                )[0]
            out_values.append(v)
        if decoders and op in ("min", "max"):
            # pandas dtype rules: a pure string-column frame keeps the string
            # dtype (even when every result is NaN); any mix is object
            if len(decoders) == len(positions):
                col_dts = {
                    str(frame._columns[i].pandas_dtype) for i in positions
                }
                dtype_arg = (
                    frame._columns[positions[0]].pandas_dtype
                    if len(col_dts) == 1
                    else object
                )
            else:
                dtype_arg = object
            result = pandas.Series(out_values, index=labels, dtype=dtype_arg)
        else:
            result = pandas.Series(out_values, index=labels)
        if op in ("any", "all"):
            result = result.astype(bool)
        elif op == "count":
            result = result.astype(np.int64)
        name = MODIN_UNNAMED_SERIES_LABEL
        return type(self).from_pandas(result.to_frame(name))

    # ---------------- sort/search-shaped device reductions ---------------- #
    # graftsort: the axis-0 families below plan a per-column strategy
    # (dictionary O(1) / O(n) histogram / shared sorted representation —
    # ops/reductions.plan_sort_reduce), then ask the kernel router
    # (ops/router.py) whether the device plan or the pandas host kernel is
    # predicted faster on this substrate; "host" declines through the
    # @device_path("sort_reduce") fallback seam.

    def _sort_reduce_specs(
        self, numeric_only: bool = False
    ) -> Optional[Tuple[list, dict]]:
        """(specs for plan_sort_reduce, {position: DictEncoding}) over all
        columns, or None when some column can join neither as a numeric
        device column nor through its dictionary encoding."""
        frame = self._modin_frame
        specs: list = []
        decoders: dict = {}
        for i, c in enumerate(frame._columns):
            if c.is_device and c.pandas_dtype.kind in "biuf":
                specs.append({"col": c})
                continue
            if (
                numeric_only
                or c.is_device
                or isinstance(c.pandas_dtype, pandas.CategoricalDtype)
            ):
                return None
            from modin_tpu.ops.dictionary import encode_host_column

            enc = encode_host_column(c)
            if enc is None:
                return None
            decoders[i] = enc
            specs.append(
                {
                    "col": enc.codes,
                    "n_categories": len(enc.categories),
                    "has_nan": enc.has_nan,
                }
            )
        return specs, decoders

    @device_path("sort_reduce")
    def _try_sort_reduce_nunique(
        self, dropna: bool
    ) -> Optional["TpuQueryCompiler"]:
        """Distinct count per column: dictionary encodings answer O(1)
        (categories ARE the distinct non-missing values), bounded-range
        ints via one O(n) histogram, the rest via the shared sorted
        representation; router-gated."""
        from modin_tpu.ops import reductions
        from modin_tpu.ops.router import decide, forced_host

        frame = self._modin_frame
        if not frame.num_cols:
            return None
        if forced_host("nunique", len(frame)):
            return None  # before any device work (materialize, range probe)
        got = self._sort_reduce_specs()
        if got is None:
            return None
        specs, _ = got
        frame.materialize_device()
        n = len(frame)
        # graftview: whole-result artifacts answer cached columns with zero
        # device work (no histogram probe, no sort) and plan as "view"
        from modin_tpu.views import reduce_cache as view_reduce

        keyed = [
            spec["col"] if "n_categories" not in spec else None
            for spec in specs
        ]
        nu_params = (bool(dropna),)
        cached_vals = (
            view_reduce.sort_reduce_lookup("nunique", nu_params, keyed)
            if graftview.VIEWS_ON
            else {}
        )
        miss_is = [i for i in range(len(specs)) if i not in cached_vals]
        plans = reductions.plan_sort_reduce(
            "nunique", [specs[i] for i in miss_is], n
        )
        strategies = ["view"] * len(cached_vals) + [p.strategy for p in plans]
        if decide("nunique", n, strategies) == "host":
            return None
        view_reduce.sort_reduce_consume("nunique", nu_params, keyed, cached_vals)
        sub_counts = reductions.nunique_planned(plans, n, bool(dropna))
        counts: list = [None] * len(specs)
        for i, v, p in zip(miss_is, sub_counts, plans):
            counts[i] = v
            if graftview.VIEWS_ON and keyed[i] is not None and p.strategy != "dict":
                view_reduce.sort_reduce_store("nunique", nu_params, keyed[i], v)
        for i, v in cached_vals.items():
            counts[i] = v
        result = pandas.Series(counts, index=frame.columns, dtype=np.int64)
        return type(self).from_pandas(
            result.to_frame(MODIN_UNNAMED_SERIES_LABEL)
        )

    def nunique(self, axis: int = 0, dropna: bool = True, **kwargs: Any):
        frame = self._modin_frame
        if axis == 0 and not kwargs and len(frame):
            result = self._try_sort_reduce_nunique(bool(dropna))
            if result is not None:
                return result
        if (
            axis == 1
            and not kwargs
            and len(frame)
            and 1 <= frame.num_cols <= 64
            and all(
                c.is_device and c.pandas_dtype.kind in "biuf"
                for c in frame._columns
            )
        ):
            from modin_tpu.ops.reductions import nunique_axis1

            frame.materialize_device()
            data = nunique_axis1(
                [c.data for c in frame._columns], len(frame), bool(dropna)
            )
            result_col = DeviceColumn(data, np.dtype(np.int64), length=len(frame))
            result_frame = TpuDataframe(
                [result_col],
                pandas.Index([MODIN_UNNAMED_SERIES_LABEL]),
                frame._index,
            )
            qc = type(self)(result_frame)
            qc._shape_hint = "column"
            return qc
        return super().nunique(axis=axis, dropna=dropna, **kwargs)

    @device_path("sort_reduce")
    def _try_sort_reduce_mode(
        self, numeric_only: bool, dropna: bool
    ) -> Optional["TpuQueryCompiler"]:
        """Modal values per column: bounded-range ints and dictionary codes
        via O(n) histograms (no sort, and no ``k_bound`` cap — every modal
        value falls out of the bin mask), the rest via the shared sorted
        representation's run-length kernel; router-gated.

        Parity surface: pandas ``DataFrame.mode`` (reference defaults it to
        a full-column fold, modin/core/storage_formats/pandas/
        query_compiler.py).  ``dropna=False`` (NaN competes for the max
        count) is supported only where every column planned "hist" — the
        sorted kernel stays dropna-only."""
        from modin_tpu.ops import reductions
        from modin_tpu.ops.router import decide, forced_host

        frame = self._modin_frame
        if forced_host("mode", len(frame)):
            return None  # before any device work (materialize, range probe)
        got = self._sort_reduce_specs(numeric_only=bool(numeric_only))
        if got is None:
            return None
        specs, decoders = got
        frame.materialize_device()
        n = len(frame)
        # graftview: cached per-column (modal values, nan_modal) artifacts
        # skip device work entirely and plan as "view"
        from modin_tpu.views import reduce_cache as view_reduce

        keyed = [
            spec["col"] if "n_categories" not in spec else None
            for spec in specs
        ]
        mode_params = (bool(dropna),)
        cached_vals = (
            view_reduce.sort_reduce_lookup("mode", mode_params, keyed)
            if graftview.VIEWS_ON
            else {}
        )
        miss_is = [i for i in range(len(specs)) if i not in cached_vals]
        plans = reductions.plan_sort_reduce(
            "mode", [specs[i] for i in miss_is], n
        )
        if not dropna and any(p.strategy != "hist" for p in plans):
            return None  # NaN-counting mode needs the histogram everywhere
        strategies = ["view"] * len(cached_vals) + [p.strategy for p in plans]
        if decide("mode", n, strategies) == "host":
            return None
        view_reduce.sort_reduce_consume("mode", mode_params, keyed, cached_vals)
        sub_cols = reductions.mode_planned(plans, n, bool(dropna))
        per_col: list = [None] * len(specs)
        for i, v, p in zip(miss_is, sub_cols, plans):
            per_col[i] = v
            if (
                graftview.VIEWS_ON
                and v is not None
                and keyed[i] is not None
                and p.strategy != "dict"
            ):
                view_reduce.sort_reduce_store("mode", mode_params, keyed[i], v)
        for i, v in cached_vals.items():
            per_col[i] = v
        if any(v is None for v in per_col):
            return None
        pieces = []
        for i, (got_col, col, label) in enumerate(
            zip(per_col, frame._columns, frame.columns)
        ):
            values, nan_modal = got_col
            if i in decoders:
                cats = decoders[i].categories
                idx = np.asarray(values).astype(np.int64)
                decoded = list(cats[idx]) if len(idx) else []
                if nan_modal:
                    # pandas keeps the column's OWN first missing object
                    # (None stays None, np.nan stays np.nan), sorted last
                    host_vals = np.asarray(col.data, dtype=object)
                    na_pos = np.flatnonzero(pandas.isna(host_vals))
                    decoded.append(
                        host_vals[na_pos[0]] if len(na_pos) else np.nan
                    )
                pieces.append(
                    pandas.Series(decoded, dtype=col.pandas_dtype, name=label)
                )
            else:
                pieces.append(
                    pandas.Series(
                        np.asarray(values).astype(col.pandas_dtype, copy=False),
                        name=label,
                    )
                )
        result = pandas.concat(pieces, axis=1)
        result.columns = frame.columns
        return type(self).from_pandas(result)

    def mode(
        self,
        axis: int = 0,
        numeric_only: bool = False,
        dropna: bool = True,
        **kwargs: Any,
    ):
        frame = self._modin_frame
        if axis == 0 and not kwargs and len(frame) and frame.num_cols:
            result = self._try_sort_reduce_mode(bool(numeric_only), bool(dropna))
            if result is not None:
                return result
        device_ok = (
            dropna
            and not kwargs
            and len(frame)
            and frame.num_cols
            and all(
                c.is_device and c.pandas_dtype.kind in "biuf"
                for c in frame._columns
            )
        )
        if device_ok and axis == 1 and frame.num_cols <= 64:
            from modin_tpu.ops.reductions import mode_axis1

            frame.materialize_device()
            vals, vals_f, m_max, uniform = mode_axis1(
                [c.data for c in frame._columns], len(frame)
            )
            if m_max > 0:
                integral = all(
                    c.pandas_dtype.kind in "biu" for c in frame._columns
                )
                matrix = vals if uniform else vals_f
                out_dtype = (
                    np.dtype(np.int64)
                    if (uniform and integral)
                    else np.dtype(np.float64)
                )
                cols = []
                for j in range(m_max):
                    data = matrix[:, j]
                    if uniform and integral:
                        data = data.astype(np.int64)
                    cols.append(
                        DeviceColumn(data, out_dtype, length=len(frame))
                    )
                result_frame = TpuDataframe(
                    cols, pandas.RangeIndex(m_max), frame._index
                )
                return type(self)(result_frame)
        return super().mode(
            axis=axis, numeric_only=numeric_only, dropna=dropna, **kwargs
        )

    def describe(
        self, percentiles: Any = None, include: Any = None, exclude: Any = None
    ):
        """Numeric describe = count/mean/std + quantiles + min/max, every
        piece an existing device kernel, assembled into the 8-row pandas
        layout host-side (census: 7 hits).  Non-numeric columns and
        include/exclude selections keep the pandas fallback."""
        frame = self._modin_frame
        if percentiles is None:
            qs = [0.25, 0.5, 0.75]
        else:
            try:
                # pandas 3 uses the given percentiles verbatim (no implicit
                # median insertion)
                qs = sorted(float(p) for p in percentiles)
            except (TypeError, ValueError):
                qs = None
            if qs is not None and len(set(qs)) != len(qs):
                qs = None  # pandas raises on duplicate percentiles
        if (
            qs is not None
            and include is None
            and exclude is None
            and len(frame)
            and frame.num_cols
            and all(
                c.is_device and c.pandas_dtype.kind in "iuf"
                for c in frame._columns
            )
            and all(0.0 <= q <= 1.0 for q in qs)
            # the quantile leg is a sort-shaped kernel: the same router
            # verdict that gates quantile() gates describe's device path
            # (a substrate where the device sort loses must not pay it
            # here either)
            and self._describe_routed_device()
        ):
            from modin_tpu.ops.reductions import quantile_columns, reduce_columns

            frame.materialize_device()
            arrays = [c.raw for c in frame._columns]
            n = len(frame)
            stats = {}
            for op in ("count", "mean", "std", "min", "max"):
                vals = reduce_columns(op, arrays, n, skipna=True, ddof=1)
                stats[op] = [float(np.asarray(v)) for v in vals]
            # columns, not raw arrays: the quantiles consume (and seed) the
            # shared sorted representation alongside the other stats
            qvals = quantile_columns(list(frame._columns), n, qs, "linear")
            rows = ["count", "mean", "std", "min"]
            data_rows = [stats["count"], stats["mean"], stats["std"], stats["min"]]
            for j, q in enumerate(qs):
                rows.append(f"{q * 100:g}%")
                data_rows.append([float(v[j]) for v in qvals])
            rows.append("max")
            data_rows.append(stats["max"])
            result = pandas.DataFrame(
                np.asarray(data_rows, dtype=np.float64),
                index=pandas.Index(rows),
                columns=frame.columns,
            )
            return type(self).from_pandas(result)
        return super().describe(
            percentiles=percentiles, include=include, exclude=exclude
        )

    def _describe_routed_device(self) -> bool:
        """Kernel-router verdict for describe's quantile leg (the
        sort-shaped piece; the count/mean/std/min/max reductions are
        cheap either way)."""
        from modin_tpu.ops import sorted_cache
        from modin_tpu.ops.router import decide, forced_host

        frame = self._modin_frame
        if forced_host("quantile", len(frame)):
            return False
        strategies = [
            "cached" if sorted_cache.peek(c) else "sort"
            for c in frame._columns
        ]
        return decide("quantile", len(frame), strategies) == "device"

    def setitem_bool(self, row_loc: Any, col_loc: Any, item: Any):
        """``df.loc[mask, col] = scalar`` as one fused where-kernel.

        pandas 3 never upcasts in loc-setitem (incompatible scalars RAISE),
        so the device path takes only dtype-preserving assignments: int
        scalars into int columns, int/float into float, bool into bool —
        everything else falls back and reproduces pandas' error.  Census: 6
        hits in the Kaggle banding pattern (loc[age <= 16, "Age"] = 0)."""
        from modin_tpu.utils import hashable

        frame = self._modin_frame
        ok = (
            isinstance(row_loc, TpuQueryCompiler)
            and row_loc._modin_frame.num_cols == 1
            and len(row_loc._modin_frame) == len(frame)
            and len(frame) > 0
            and self._fast_index_match(row_loc)
            and hashable(col_loc)
        )
        if ok:
            mcol = row_loc._modin_frame.get_column(0)
            pos = frame.column_position(col_loc)
            ok = (
                mcol.is_device
                and mcol.pandas_dtype == np.dtype(bool)
                and len(pos) == 1
                and pos[0] >= 0
            )
        if ok:
            col = frame._columns[pos[0]]
            kind = col.pandas_dtype.kind if col.is_device else ""
            is_bool = isinstance(item, (bool, np.bool_))
            if kind == "b":
                ok = is_bool
            elif kind in "iu":
                ok = isinstance(item, (int, np.integer)) and not is_bool
                if ok:
                    info = np.iinfo(col.pandas_dtype)
                    # out-of-range would wrap on device; pandas 3 raises
                    ok = info.min <= int(item) <= info.max
            elif kind == "f":
                ok = (
                    isinstance(item, (int, float, np.integer, np.floating))
                    and not is_bool
                )
            else:
                ok = False
        if ok:
            import jax.numpy as jnp

            frame.materialize_device()
            row_loc._modin_frame.materialize_device()
            new_data = jnp.where(
                mcol.data,
                jnp.asarray(item, col.data.dtype),
                col.data,
            )
            new_cols = list(frame._columns)
            new_cols[pos[0]] = DeviceColumn(
                new_data, col.pandas_dtype, length=len(frame)
            )
            return type(self)(
                TpuDataframe(
                    new_cols, frame._col_labels, frame._index, nrows=len(frame)
                )
            )
        return super().setitem_bool(row_loc, col_loc, item)

    def unique(self, **kwargs: Any):
        """String-series unique via the dictionary encoding: categories are
        the distinct values; APPEARANCE order (pandas' contract) comes from a
        device segment-min of first positions per code."""
        frame = self._modin_frame
        col = frame.get_column(0) if frame.num_cols == 1 else None
        if col is not None and not col.is_device and len(frame) and not kwargs:
            from modin_tpu.ops.dictionary import decode_codes, encode_host_column

            enc = encode_host_column(col)
            if enc is not None:
                import jax

                from modin_tpu.ops import groupby as gb_ops

                try:
                    codes, n_groups, group_keys, _ = gb_ops.factorize_keys_cached(
                        [enc.codes.data], len(frame), dropna=False
                    )
                except gb_ops._TooManyGroups:
                    return super().unique(**kwargs)
                first_dev = gb_ops.groupby_first_position(codes, n_groups)
                first = np.asarray(_engine_materialize(first_dev))[:n_groups]
                order = np.argsort(first, kind="stable")
                values = decode_codes(
                    np.asarray(group_keys[0], np.float64)[order], enc.categories
                )
                if isinstance(col.pandas_dtype, pandas.StringDtype):
                    # NA-backed string series surface pd.NA, not np.nan
                    result = pandas.Series(
                        pandas.array(values, dtype=col.pandas_dtype)
                    )
                else:
                    result = pandas.Series(values, dtype=object)
                return type(self).from_pandas(
                    result.to_frame(MODIN_UNNAMED_SERIES_LABEL)
                )
        return super().unique(**kwargs)

    def series_get_dummies(
        self,
        prefix: Any = None,
        prefix_sep: str = "_",
        dummy_na: bool = False,
        drop_first: bool = False,
        dtype: Any = None,
    ):
        """One-hot encode a string/categorical Series on device: one
        ``codes == k`` kernel per category (bounded at 256), columns in
        pandas' order (sorted uniques for strings, category order — with
        unobserved categories — for categoricals).  Returns None when not
        applicable so the caller can fall back."""
        frame = self._modin_frame
        col = frame.get_column(0) if frame.num_cols == 1 else None
        if col is None or not len(frame):
            return None
        if isinstance(col.pandas_dtype, pandas.CategoricalDtype):
            # the resident integer codes (-1 = missing) serve as they are
            from modin_tpu.ops.dictionary import resident_category_column

            resident = resident_category_column(col)
            if resident is None:
                return None
            frame._columns[0] = resident
            codes, cats = resident.data, list(col.pandas_dtype.categories)
        elif col.is_device:
            return None
        else:
            from modin_tpu.ops.dictionary import encode_host_column

            enc = encode_host_column(col)
            if enc is None:
                return None
            # float codes, NaN = missing
            codes, cats = enc.codes.data, list(enc.categories)
        if not (0 < len(cats) <= 256):
            return None
        out_dtype = np.dtype(bool) if dtype is None else np.dtype(dtype)
        if out_dtype.kind not in "biuf":
            return None
        import jax.numpy as jnp

        labels: list = []
        cols: list = []
        start = 1 if drop_first else 0
        for k, cat in enumerate(cats):
            if k < start:
                continue
            data = codes == k
            if out_dtype != np.dtype(bool):
                data = data.astype(jnp.dtype(out_dtype.name))
            cols.append(DeviceColumn(data, out_dtype, length=len(frame)))
            labels.append(
                f"{prefix}{prefix_sep}{cat}" if prefix is not None else cat
            )
        if dummy_na:
            data = (
                jnp.isnan(codes)
                if jnp.issubdtype(codes.dtype, jnp.floating)
                else codes < 0
            )
            if out_dtype != np.dtype(bool):
                data = data.astype(jnp.dtype(out_dtype.name))
            cols.append(DeviceColumn(data, out_dtype, length=len(frame)))
            labels.append(
                f"{prefix}{prefix_sep}nan" if prefix is not None else np.nan
            )
        if not cols:
            return None
        if isinstance(col.pandas_dtype, pandas.CategoricalDtype) and prefix is None:
            # pandas labels categorical dummies with a CategoricalIndex
            # (the dummy_na column's NaN label is the -1 code)
            label_index: pandas.Index = pandas.CategoricalIndex(
                labels, dtype=col.pandas_dtype
            )
        else:
            label_index = pandas.Index(labels)
        return type(self)(
            TpuDataframe(cols, label_index, frame._index, nrows=len(frame))
        )

    @device_path("dt_component")
    def _try_dt_component(self, name: str, args: tuple, kwargs: dict):
        """Calendar components of a datetime64 Series as one device kernel
        (ops/datetime_parts.py — branchless civil-date decomposition over
        the int64 ticks; the reference extracts host-side via pandas tslib
        per partition).  Naive datetimes only; tz-aware stay host."""
        if args or kwargs:
            return None
        frame = self._modin_frame
        col = frame.get_column(0) if frame.num_cols == 1 else None
        if (
            col is None
            or not col.is_device
            or col.pandas_dtype.kind != "M"
            or not len(frame)
        ):
            return None
        from modin_tpu.ops.datetime_parts import COMPONENT_NAMES, dt_component

        if name not in COMPONENT_NAMES:
            return None
        unit = np.datetime_data(col.pandas_dtype)[0]
        if unit not in ("s", "ms", "us", "ns"):
            return None
        frame.materialize_device()
        data, out_dtype = dt_component(name, col.data, unit, len(frame))
        result_col = DeviceColumn(data, out_dtype, length=len(frame))
        qc = type(self)(
            TpuDataframe(
                [result_col], frame._col_labels, frame._index, nrows=len(frame)
            )
        )
        qc._shape_hint = "column"
        return qc

    @device_path("dt_component")
    def _try_td_component(self, name: str, args: tuple, kwargs: dict):
        """Timedelta fields (days/seconds/microseconds/nanoseconds,
        total_seconds) over the int64 ticks — same design as
        _try_dt_component for datetime columns."""
        if args or kwargs:
            return None
        frame = self._modin_frame
        col = frame.get_column(0) if frame.num_cols == 1 else None
        if (
            col is None
            or not col.is_device
            or col.pandas_dtype.kind != "m"
            or not len(frame)
        ):
            return None
        from modin_tpu.ops.datetime_parts import (
            TIMEDELTA_COMPONENT_NAMES,
            td_component,
        )

        if name not in TIMEDELTA_COMPONENT_NAMES:
            return None
        unit = np.datetime_data(col.pandas_dtype)[0]
        if unit not in ("s", "ms", "us", "ns"):
            return None
        frame.materialize_device()
        data, out_dtype = td_component(name, col.data, unit, len(frame))
        result_col = DeviceColumn(data, out_dtype, length=len(frame))
        qc = type(self)(
            TpuDataframe(
                [result_col], frame._col_labels, frame._index, nrows=len(frame)
            )
        )
        qc._shape_hint = "column"
        return qc

    @device_path("str_lut")
    def _try_str_lut(self, name: str, args: tuple, kwargs: dict):
        """String predicates/measures through the dictionary encoding: the
        pandas op runs once per CATEGORY (host, tiny), and the result lookup
        table gathers by code on device — ``.str.len()`` & co. never touch
        the n rows.  Missing rows take whatever pandas produces for a NaN
        probe of the column's dtype (bool fill for str-dtype/na= kwargs,
        NaN for numeric ops); a NaN probe yielding NaN under a bool op means
        pandas' object-mixed output, which stays on the fallback."""
        frame = self._modin_frame
        col = frame.get_column(0) if frame.num_cols == 1 else None
        if col is None or col.is_device or not len(frame):
            return None
        if (
            isinstance(col.pandas_dtype, pandas.StringDtype)
            and col.pandas_dtype.na_value is pandas.NA
        ):
            # NA-backed 'string' dtype: pandas emits Int64/boolean EXTENSION
            # results here, not numpy int64/bool — keep the pandas fallback
            return None
        from modin_tpu.ops.dictionary import encode_host_column

        enc = encode_host_column(col)
        if enc is None:
            return None
        try:
            cats = pandas.Series(enc.categories, dtype=col.pandas_dtype)
            lut_ser = getattr(cats.str, name)(*args, **kwargs)
            na_probe = None
            if enc.has_nan:
                na_probe = getattr(
                    pandas.Series([np.nan], dtype=col.pandas_dtype).str, name
                )(*args, **kwargs).iloc[0]
        except (
            TypeError,
            ValueError,
            AttributeError,
            NotImplementedError,
            KeyError,
            re.error,
        ):
            # the semantic "pandas declined this str op / these kwargs"
            # family only — a device failure during the later gather must
            # reach the resilience layer, not read as a silent fallback
            return None
        if (
            not isinstance(lut_ser, pandas.Series)
            or len(lut_ser) != len(enc.categories)
        ):
            return None
        import jax.numpy as jnp

        kind = getattr(lut_ser.dtype, "kind", "")
        cast = None
        if kind == "b":
            if enc.has_nan:
                if not isinstance(na_probe, (bool, np.bool_)):
                    return None  # NaN-mixed object output
                fill = float(bool(na_probe))
            else:
                fill = 0.0
            lut = np.append(lut_ser.to_numpy().astype(np.float64), fill)
            out_dtype = np.dtype(bool)
            cast = jnp.bool_
        elif kind in "iuf":
            vals = lut_ser.to_numpy().astype(np.float64)
            if enc.has_nan:
                if na_probe is None or (
                    isinstance(na_probe, (float, np.floating))
                    and np.isnan(na_probe)
                ):
                    fill = np.nan
                elif isinstance(
                    na_probe, (int, float, np.integer, np.floating)
                ):
                    fill = float(na_probe)
                else:
                    return None
            else:
                fill = np.nan  # unreachable slot
            lut = np.append(vals, fill)
            if kind in "iu" and not np.isnan(lut[: len(vals) + int(enc.has_nan)]).any():
                out_dtype = np.dtype(np.int64)
                cast = jnp.int64
            else:
                out_dtype = np.dtype(np.float64)
        else:
            return None  # string/object outputs stay host
        codes = enc.codes.data
        safe = jnp.where(jnp.isnan(codes), len(enc.categories), codes)
        data = jnp.take(jnp.asarray(lut), safe.astype(jnp.int32), mode="clip")
        if cast is not None:
            data = data.astype(cast)
        result_col = DeviceColumn(data, out_dtype, length=len(frame))
        qc = type(self)(
            TpuDataframe(
                [result_col], frame._col_labels, frame._index, nrows=len(frame)
            )
        )
        qc._shape_hint = "column"
        return qc

    def series_map(self, arg: Any, na_action: Any = None) -> "TpuQueryCompiler":
        """dict-mapping a Series on device.

        String/object columns translate their CATEGORIES through the mapping
        (host, |categories| lookups) and gather the resulting numeric lookup
        table by code on device — the Kaggle recode pattern
        (``s.map({"male": 0, "female": 1})``) without materializing rows.
        Numeric columns use one sorted-keys searchsorted kernel.  Object
        outputs, NaN dict keys, and non-dict args keep the pandas fallback
        (base census: 5 hits)."""
        frame = self._modin_frame
        col = frame.get_column(0) if frame.num_cols == 1 else None
        if isinstance(arg, pandas.Series) and arg.index.is_unique:
            arg = arg.to_dict()
        numeric_types = (int, float, bool, np.integer, np.floating, np.bool_)

        def _is_nan_key(k):
            return isinstance(k, (float, np.floating)) and np.isnan(k)

        if (
            col is not None
            and type(arg) is dict  # subclasses may define __missing__
            and len(frame)
            and not any(_is_nan_key(k) for k in arg)
            and all(
                v is None or isinstance(v, numeric_types) for v in arg.values()
            )
        ):
            import jax.numpy as jnp

            clean_vals = [v for v in arg.values() if v is not None]
            all_bool = bool(clean_vals) and all(
                isinstance(v, (bool, np.bool_)) for v in clean_vals
            )
            all_int = bool(clean_vals) and all(
                isinstance(v, (int, bool, np.integer, np.bool_))
                and not isinstance(v, (float, np.floating))
                for v in clean_vals
            )
            data = None
            if not col.is_device:
                from modin_tpu.ops.dictionary import encode_host_column

                enc = encode_host_column(col)
                if enc is not None:
                    lut = np.full(len(enc.categories) + 1, np.nan, np.float64)
                    matched = np.zeros(len(enc.categories) + 1, bool)
                    for i, c in enumerate(enc.categories):
                        if c in arg:
                            v = arg[c]
                            lut[i] = np.nan if v is None else float(v)
                            matched[i] = v is not None
                    codes = enc.codes.data
                    safe = jnp.where(jnp.isnan(codes), len(enc.categories), codes)
                    safe = safe.astype(jnp.int32)
                    data = jnp.take(jnp.asarray(lut), safe, mode="clip")
                    fully = bool(matched[:-1].all()) and not enc.has_nan
            elif col.is_device and col.pandas_dtype.kind in "biuf":
                try:
                    ks = np.asarray(sorted(arg.keys()))
                except TypeError:
                    ks = None
                if ks is not None and ks.dtype.kind in "biuf" and len(ks):
                    frame.materialize_device()
                    vs = np.asarray(
                        [
                            np.nan if arg[k] is None else float(arg[k])
                            for k in ks
                        ],
                        np.float64,
                    )
                    x = col.data.astype(jnp.float64)
                    pos = jnp.clip(
                        jnp.searchsorted(jnp.asarray(ks.astype(np.float64)), x),
                        0,
                        len(ks) - 1,
                    )
                    hit = jnp.asarray(ks.astype(np.float64))[pos] == x
                    data = jnp.where(
                        hit, jnp.take(jnp.asarray(vs), pos), jnp.nan
                    )
                    # int result only when every VALID row matched an int
                    # value (pad rows must not veto)
                    import jax as _jax

                    valid = jnp.arange(x.shape[0]) < len(frame)
                    fully = all_int and bool(
                        _engine_materialize(jnp.all(hit | ~valid))
                    )
            if data is not None:
                if all_bool and not fully:
                    # pandas yields OBJECT True/False/NaN here, not floats
                    return super().series_map(arg, na_action=na_action)
                out_dtype = np.dtype(np.float64)
                if all_bool and fully:
                    data = data.astype(jnp.bool_)
                    out_dtype = np.dtype(bool)
                elif all_int and fully:
                    data = data.astype(jnp.int64)
                    out_dtype = np.dtype(np.int64)
                result_col = DeviceColumn(data, out_dtype, length=len(frame))
                result_frame = TpuDataframe(
                    [result_col], frame._col_labels, frame._index,
                    nrows=len(frame),
                )
                qc = type(self)(result_frame)
                qc._shape_hint = "column"
                return qc
        return super().series_map(arg, na_action=na_action)

    def reset_index(self, **kwargs: Any):
        """drop=True is pure metadata (swap in a RangeIndex, zero device
        work); drop=False prepends the index levels as columns (numeric
        levels device_put, object levels stay host).  The top fallback in
        the Kaggle-workflow census (13 hits) before this path existed."""
        drop = kwargs.get("drop", False)
        unsupported = any(
            (
                (k == "level" and v is not None)
                or (k == "names" and v is not None)
                or (k == "col_level" and v not in (0,))
                or (k == "col_fill" and v not in ("",))
                or (
                    k == "allow_duplicates"
                    and v is not False
                    and v is not pandas.api.extensions.no_default
                )
            )
            for k, v in kwargs.items()
        )
        frame = self._modin_frame
        n = len(frame)
        if unsupported or isinstance(frame.columns, pandas.MultiIndex):
            return super().reset_index(**kwargs)
        if drop:
            return type(self)(
                TpuDataframe(
                    list(frame._columns),
                    frame._col_labels,
                    LazyIndex(pandas.RangeIndex(n), n),
                    nrows=n,
                )
            )
        idx = frame.index
        if isinstance(idx, pandas.MultiIndex):
            levels = [idx.get_level_values(i) for i in range(idx.nlevels)]
            names = [
                nm if nm is not None else f"level_{i}"
                for i, nm in enumerate(idx.names)
            ]
        else:
            levels = [idx]
            names = [
                idx.name
                if idx.name is not None
                else ("index" if "index" not in set(frame.columns) else "level_0")
            ]
        if any(nm in set(frame.columns) for nm in names):
            return super().reset_index(**kwargs)  # pandas raises/renames
        from modin_tpu.core.dataframe.tpu.dataframe import _is_device_dtype

        new_cols: list = []
        for lv in levels:
            # decide by the LEVEL dtype, not to_numpy()'s: a categorical of
            # int labels to_numpy()s as int64 and would lose its dtype
            if isinstance(lv.dtype, np.dtype) and _is_device_dtype(lv.dtype):
                new_cols.append(DeviceColumn.from_numpy(lv.to_numpy()))
            else:
                new_cols.append(HostColumn(lv.array.copy()))
        new_cols.extend(frame._columns)
        labels = pandas.Index(list(names) + list(frame.columns))
        return type(self)(
            TpuDataframe(
                new_cols, labels, LazyIndex(pandas.RangeIndex(n), n), nrows=n
            )
        )

    # Beyond this many resulting columns a transpose leaves the columnar
    # device store: per-column objects at 1e5+ columns cost minutes to build
    # and gigabytes of Python overhead, so the wide result rides a host
    # (Native) compiler instead — the per-method caster handles the mixed
    # backends downstream.
    _TRANSPOSE_WIDE_COLS = 4096

    def transpose(self, *args: Any, **kwargs: Any):
        if len(self._modin_frame) > self._TRANSPOSE_WIDE_COLS:
            from modin_tpu.core.storage_formats.native.query_compiler import (
                NativeQueryCompiler,
            )

            return NativeQueryCompiler(self.to_pandas().T)
        return super().transpose(*args, **kwargs)

    def quantile(
        self,
        q: Any = 0.5,
        axis: int = 0,
        numeric_only: bool = False,
        interpolation: str = "linear",
        method: str = "single",
        **kwargs: Any,
    ):
        from pandas.api.types import is_list_like

        frame = self._modin_frame
        qs = list(q) if is_list_like(q) else [q]
        device_ok = (
            axis == 0
            and method == "single"
            and not kwargs
            and len(frame)
            and interpolation in ("linear", "lower", "higher", "midpoint", "nearest")
            and all(isinstance(v, (int, float, np.integer, np.floating)) for v in qs)
            and all(0 <= float(v) <= 1 for v in qs)
        )
        if device_ok:
            result = self._try_sort_reduce_quantile(
                q, [float(v) for v in qs], str(interpolation),
                bool(numeric_only), bool(is_list_like(q)),
            )
            if result is not None:
                return result
        return super().quantile(
            q=q, axis=axis, numeric_only=numeric_only,
            interpolation=interpolation, method=method, **kwargs,
        )

    @device_path("sort_reduce")
    def _try_sort_reduce_quantile(
        self, q: Any, qs: list, interpolation: str, numeric_only: bool,
        list_like: bool,
    ) -> Optional["TpuQueryCompiler"]:
        """Quantiles over the shared sorted representation (one sort per
        column amortized across the whole sort-shaped family); router-gated."""
        from modin_tpu.ops import sorted_cache
        from modin_tpu.ops.reductions import quantile_columns
        from modin_tpu.ops.router import decide, forced_host

        frame = self._modin_frame
        if forced_host("quantile", len(frame)):
            return None  # before any device work (materialization)
        positions = []
        for i, col in enumerate(frame._columns):
            # bool columns: pandas quantile RAISES on them — fallback
            if col.is_device and col.pandas_dtype.kind in "iuf":
                positions.append(i)
            elif numeric_only and col.pandas_dtype.kind not in "biufc":
                continue  # pandas drops it
            else:
                return None
        if not positions:
            return None
        frame.materialize_device()
        cols = [frame._columns[i] for i in positions]
        strategies = [
            "cached" if sorted_cache.peek(c) else "sort" for c in cols
        ]
        if decide("quantile", len(frame), strategies) == "host":
            return None
        vals = quantile_columns(cols, len(frame), qs, interpolation)
        labels = frame.columns[positions]
        if list_like:
            # positional dict first: duplicate labels must survive
            result = pandas.DataFrame(
                dict(enumerate(vals)), index=pandas.Index(qs)
            )
            result.columns = labels
            return type(self).from_pandas(result)
        result = pandas.Series([arr[0] for arr in vals], index=labels, name=q)
        return type(self).from_pandas(result.to_frame())

    @device_path("top_k")
    def _try_device_top_k(self, n: int, column_pos: int, largest: bool, keep: str):
        from modin_tpu.ops.sort import top_k_positions

        frame = self._modin_frame
        if keep != "first" or len(frame) == 0:
            return None
        col = frame._columns[column_pos]
        if not col.is_device or col.pandas_dtype.kind not in "biuf":
            return None
        frame.materialize_device()
        positions, _ = top_k_positions(col.data, len(frame), int(n), bool(largest))
        return type(self)(frame.take_rows_positional(positions))

    def nlargest(self, n: int = 5, columns: Any = None, keep: str = "first", **kwargs: Any):
        result = self._top_k_dispatch(n, columns, keep, kwargs, largest=True)
        if result is not None:
            return result
        return super().nlargest(n=n, columns=columns, keep=keep, **kwargs)

    def nsmallest(self, n: int = 5, columns: Any = None, keep: str = "first", **kwargs: Any):
        result = self._top_k_dispatch(n, columns, keep, kwargs, largest=False)
        if result is not None:
            return result
        return super().nsmallest(n=n, columns=columns, keep=keep, **kwargs)

    def _top_k_dispatch(self, n, columns, keep, kwargs, largest):
        if kwargs or not isinstance(n, (int, np.integer)) or n < 0:
            return None
        frame = self._modin_frame
        if columns is None:
            # Series form: the single data column orders itself
            if frame.num_cols != 1:
                return None
            pos = 0
        else:
            col_list = [columns] if not isinstance(columns, list) else columns
            if len(col_list) != 1:
                # multi-column tie-break chain: pandas fallback
                return None
            matches = frame.column_position(col_list[0])
            if len(matches) != 1 or matches[0] < 0:
                return None
            pos = matches[0]
        return self._try_device_top_k(int(n), pos, largest, keep)

    def series_nlargest(self, n: int = 5, keep: str = "first", **kwargs: Any):
        result = self._top_k_dispatch(n, None, keep, kwargs, largest=True)
        if result is not None:
            result._shape_hint = "column"
            return result
        return super().series_nlargest(n=n, keep=keep, **kwargs)

    def series_nsmallest(self, n: int = 5, keep: str = "first", **kwargs: Any):
        result = self._top_k_dispatch(n, None, keep, kwargs, largest=False)
        if result is not None:
            result._shape_hint = "column"
            return result
        return super().series_nsmallest(n=n, keep=keep, **kwargs)

    # both overrides take pandas-signature args verbatim, so the API routing
    # layer may dispatch into them (see _try_qc_dispatch's marker check)
    series_nlargest._pandas_signature_default = True
    series_nsmallest._pandas_signature_default = True

    def rank(
        self,
        axis: int = 0,
        method: str = "average",
        numeric_only: bool = False,
        na_option: str = "keep",
        ascending: bool = True,
        pct: bool = False,
        **kwargs: Any,
    ):
        frame = self._modin_frame
        device_ok = (
            axis in (0, None)
            and not kwargs
            and method in ("average", "min", "max", "first", "dense")
            and na_option in ("keep", "top", "bottom")
            and isinstance(ascending, (bool, np.bool_))
            and isinstance(pct, (bool, np.bool_))
            and len(frame) > 0
        )
        if device_ok:
            positions = []
            for i, col in enumerate(frame._columns):
                if col.is_device and col.pandas_dtype.kind in "biuf":
                    positions.append(i)
                elif numeric_only and col.pandas_dtype.kind not in "biufc":
                    continue  # pandas drops it
                else:
                    device_ok = False
                    break
        if device_ok and positions:
            from modin_tpu.ops.sort import rank_columns

            frame.materialize_device()
            datas = rank_columns(
                [frame._columns[i].data for i in positions], len(frame),
                method, bool(ascending), na_option, bool(pct),
            )
            return self._wrap_device_result(
                datas,
                dtypes=[np.dtype(np.float64)] * len(datas),
                col_labels=frame.columns[positions],
            )
        return super().rank(
            axis=axis, method=method, numeric_only=numeric_only,
            na_option=na_option, ascending=ascending, pct=pct, **kwargs,
        )

    def _duplicated_device_mask(self, subset: Any, keep: Any):
        """Device duplicate-row mask over the subset columns, or None when
        the gate fails (non-device/non-numeric keys, exotic keep)."""
        from modin_tpu.ops.join import duplicated_mask

        if keep not in ("first", "last", False):
            return None
        frame = self._modin_frame
        if len(frame) == 0:
            return None
        if subset is None:
            positions = list(range(frame.num_cols))
        else:
            # pandas accepts any list-like subset; a tuple stays one label
            if isinstance(subset, (list, np.ndarray, pandas.Index, pandas.Series)):
                subset_list = list(subset)
            else:
                subset_list = [subset]
            positions = []
            for label in subset_list:
                matches = frame.column_position(label)
                if len(matches) != 1 or matches[0] < 0:
                    return None  # missing/duplicate label: pandas raises
                positions.append(matches[0])
        if not positions:
            return None
        key_datas = []
        for i in positions:
            c = frame._columns[i]
            if c.is_device and c.pandas_dtype.kind in "biuf":
                key_datas.append(None)  # resolved after materialize
                continue
            if not c.is_device:
                # string/object keys compare by dictionary code (NaN codes
                # rank together like pandas' NaN==NaN duplicate rule)
                from modin_tpu.ops.dictionary import encode_host_column

                enc = encode_host_column(c)
                if enc is not None:
                    key_datas.append(enc.codes.data)
                    continue
            return None
        frame.materialize_device()
        key_datas = [
            frame._columns[i].data if d is None else d
            for i, d in zip(positions, key_datas)
        ]
        return duplicated_mask(key_datas, len(frame), keep)

    def duplicated(self, subset: Any = None, keep: Any = "first", **kwargs: Any):
        mask = (
            self._duplicated_device_mask(subset, keep) if not kwargs else None
        )
        if mask is not None:
            return self._wrap_device_result(
                [mask],
                dtypes=[np.dtype(bool)],
                col_labels=pandas.Index([MODIN_UNNAMED_SERIES_LABEL]),
            )
        return super().duplicated(subset=subset, keep=keep, **kwargs)

    def drop_duplicates(
        self,
        subset: Any = None,
        keep: Any = "first",
        ignore_index: bool = False,
        **kwargs: Any,
    ):
        mask = (
            self._duplicated_device_mask(subset, keep) if not kwargs else None
        )
        if mask is not None:
            new_frame = self._modin_frame.filter_rows_mask_device(~mask)
            if ignore_index:
                # the filter already synced the kept-count; a fresh
                # RangeIndex costs nothing and keeps device residency
                new_frame.index = pandas.RangeIndex(len(new_frame))
            return type(self)(new_frame)
        return super().drop_duplicates(
            subset=subset, keep=keep, ignore_index=ignore_index, **kwargs
        )

    def isin(self, values: Any, ignore_indices: bool = False, **kwargs: Any) -> "TpuQueryCompiler":
        frame = self._modin_frame
        scalar_list = isinstance(values, (list, tuple, set, frozenset, np.ndarray))
        if scalar_list:
            vals = list(values)
            scalar_list = 0 < len(vals) <= 1024 and all(
                isinstance(
                    v, (int, float, bool, str, np.integer, np.floating, np.bool_)
                )
                for v in vals
            )
        plans = None
        if scalar_list and not kwargs and len(frame):
            # per-column plan: numeric device columns compare raw values;
            # object/str columns compare dictionary CODES of the values that
            # exist in their categories (absent/unorderable values can't match)
            missing_vals = any(
                v is None
                or (isinstance(v, (float, np.floating)) and np.isnan(v))
                for v in vals
            )
            plans = []
            for c in frame._columns:
                if c.is_device and c.pandas_dtype.kind in "biuf":
                    plans.append((c, None, False))
                    continue
                if not c.is_device:
                    from modin_tpu.ops.dictionary import encode_host_column

                    enc = encode_host_column(c)
                    if enc is not None:
                        # object dtype keeps None and np.nan DISTINCT in
                        # pandas isin, but both encode to NaN codes: with
                        # missing rows AND a missing search value the match
                        # is undecidable post-encoding — fall back.  The
                        # str dtype unifies them (all-missing match), so
                        # its device path survives.
                        if (
                            missing_vals
                            and enc.has_nan
                            and pandas.api.types.is_object_dtype(c.pandas_dtype)
                        ):
                            plans = None
                            break
                        plans.append(
                            (enc.codes, enc.categories, missing_vals)
                        )
                        continue
                plans = None
                break
        if plans is not None:
            import jax.numpy as jnp

            from modin_tpu.ops.dictionary import lookup_values
            from modin_tpu.ops.lazy import lazy_op

            has_nan = any(
                isinstance(v, (float, np.floating)) and np.isnan(v) for v in vals
            )
            numeric = [
                v for v in vals
                if isinstance(v, (int, float, bool, np.integer, np.floating, np.bool_))
                and not (isinstance(v, (float, np.floating)) and np.isnan(v))
            ]

            clean_arr = np.asarray(numeric) if numeric else np.empty(0, np.float64)
            all_int_values = clean_arr.dtype.kind in "biu"

            def values_for(dtype: np.dtype):
                # pandas/numpy promotion: an all-integer value list compares
                # with integer columns EXACTLY (no f64 rounding of >2^53
                # entries); any float in the list promotes the comparison to
                # float64, column included — lossy, as pandas is
                if dtype.kind in "iu" and all_int_values:
                    info = np.iinfo(dtype)
                    ints = [
                        int(v) for v in clean_arr
                        if info.min <= int(v) <= info.max
                    ]
                    return _engine_upload(np.asarray(ints, dtype=dtype))
                return _engine_upload(clean_arr.astype(np.float64))

            frame.materialize_device()
            datas = []
            for col, cats, match_missing in plans:
                if cats is None:
                    op = (
                        "isin_vals_nan"
                        if has_nan and col.pandas_dtype.kind == "f"
                        else "isin_vals"
                    )
                    datas.append(
                        lazy_op(op, col.data, values_for(col.pandas_dtype))
                    )
                else:
                    code_vals = lookup_values(vals, cats)
                    code_vals = code_vals[~np.isnan(code_vals)]
                    op = "isin_vals_nan" if match_missing else "isin_vals"
                    datas.append(
                        lazy_op(op, col.data, _engine_upload(code_vals))
                    )
            return self._wrap_device_result(
                datas, dtypes=[np.dtype(bool)] * len(datas)
            )
        return super().isin(values, ignore_indices=ignore_indices, **kwargs)

    @device_path("corr_cov")
    def _try_device_corr_cov(
        self, method: str, min_periods: int, ddof: int, numeric_only: bool
    ) -> Optional["TpuQueryCompiler"]:
        """Pairwise corr/cov as masked MXU matmuls (ops/stats.py; ref
        aggregations.py:31 computes the same sums-of-products per block)."""
        from modin_tpu.ops.stats import corr_cov_matrix

        frame = self._modin_frame
        if len(frame) == 0 or frame.num_cols == 0:
            return None
        positions = []
        for i, col in enumerate(frame._columns):
            ok = col.is_device and col.pandas_dtype.kind in "biuf"
            if ok:
                positions.append(i)
            elif numeric_only and col.pandas_dtype.kind not in "biufc":
                continue
            else:
                return None
        if not positions:
            return None
        frame.materialize_device()
        arrays = [frame._columns[i].data for i in positions]
        labels = frame.columns[positions]
        mat, _ = corr_cov_matrix(
            arrays, len(frame), method=method, ddof=ddof,
            min_periods=min_periods,
        )
        return type(self).from_pandas(
            pandas.DataFrame(mat, index=labels, columns=labels)
        )

    def corr(self, method: Any = "pearson", min_periods: Any = 1, numeric_only: bool = False, **kwargs: Any) -> "TpuQueryCompiler":
        if method == "pearson" and not kwargs:
            result = self._try_device_corr_cov(
                "corr", int(min_periods) if min_periods is not None else 1,
                1, bool(numeric_only),
            )
            if result is not None:
                return result
        return super().corr(
            method=method, min_periods=min_periods, numeric_only=numeric_only,
            **kwargs,
        )

    def cov(self, min_periods: Any = None, ddof: int = 1, numeric_only: bool = False, **kwargs: Any) -> "TpuQueryCompiler":
        if not kwargs and isinstance(ddof, (int, np.integer)):
            result = self._try_device_corr_cov(
                "cov", int(min_periods) if min_periods is not None else 1,
                int(ddof), bool(numeric_only),
            )
            if result is not None:
                return result
        return super().cov(
            min_periods=min_periods, ddof=ddof, numeric_only=numeric_only,
            **kwargs,
        )

    def _device_idx_minmax(self, op: str, axis: int, skipna: bool, numeric_only: bool, kwargs: dict):
        from modin_tpu.ops import reductions

        frame = self._modin_frame
        if (
            axis == 0
            and skipna
            and len(frame) > 0
            and all(c.is_device and c.pandas_dtype.kind in "iuf" for c in frame._columns)
        ):
            frame.materialize_device()
            positions, valid_counts = reductions.idx_minmax(
                op, [c.data for c in frame._columns], len(frame)
            )
            if all(c > 0 for c in valid_counts):
                labels = frame.index.take(positions)
                result = pandas.Series(labels, index=frame.columns)
                return type(self).from_pandas(
                    result.to_frame(MODIN_UNNAMED_SERIES_LABEL)
                )
            # all-NaN column: pandas raises — take the fallback path
        return None

    def idxmin(self, axis: int = 0, skipna: bool = True, numeric_only: bool = False, **kwargs: Any):
        result = self._device_idx_minmax("idxmin", axis, skipna, numeric_only, kwargs)
        if result is not None:
            return result
        return super().idxmin(axis=axis, skipna=skipna, numeric_only=numeric_only, **kwargs)

    def idxmax(self, axis: int = 0, skipna: bool = True, numeric_only: bool = False, **kwargs: Any):
        result = self._device_idx_minmax("idxmax", axis, skipna, numeric_only, kwargs)
        if result is not None:
            return result
        return super().idxmax(axis=axis, skipna=skipna, numeric_only=numeric_only, **kwargs)

    # ---------------------------- shift/diff --------------------------- #

    @device_path("shift")
    def _try_shift_like(self, kernel, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        periods = kwargs.get("periods", 1)
        if (
            kwargs.get("axis", 0) not in (0, None)
            or kwargs.get("freq") is not None
            or "fill_value" in kwargs
            or not isinstance(periods, (int, np.integer))
        ):
            return None
        frame = self._modin_frame
        if len(frame) == 0 or not all(
            c.is_device and c.pandas_dtype.kind in "iuf" for c in frame._columns
        ):
            return None
        frame.materialize_device()
        datas = kernel([c.data for c in frame._columns], len(frame), int(periods))
        return self._wrap_device_result(datas)

    def shift(self, **kwargs: Any) -> "TpuQueryCompiler":
        from modin_tpu.ops.elementwise import shift_columns

        result = self._try_shift_like(shift_columns, kwargs)
        if result is not None:
            return result
        return super().shift(**kwargs)

    def diff(self, **kwargs: Any) -> "TpuQueryCompiler":
        from modin_tpu.ops.elementwise import diff_columns

        result = self._try_shift_like(diff_columns, kwargs)
        if result is not None:
            return result
        return super().diff(**kwargs)

    # ------------------------------ dropna ---------------------------- #

    def dropna(self, **kwargs: Any) -> "TpuQueryCompiler":
        axis = kwargs.get("axis", 0)
        how = kwargs.get("how", "any")
        thresh = kwargs.get("thresh")
        subset = kwargs.get("subset")
        frame = self._modin_frame
        if (
            axis == 0
            and how in ("any", "all")
            and thresh is None
            and not kwargs.get("ignore_index", False)
            and len(frame) > 0
            and all(c.is_device and not c.is_category for c in frame._columns)
        ):
            if subset is not None:
                from pandas.api.types import is_list_like

                subset_list = list(subset) if is_list_like(subset) else [subset]
                positions = []
                for label in subset_list:
                    pos = frame.column_position(label)
                    if len(pos) != 1 or pos[0] < 0:
                        return super().dropna(**kwargs)
                    positions.append(pos[0])
            else:
                positions = list(range(frame.num_cols))
            from modin_tpu.ops.elementwise import isna_columns

            cols = [frame.get_column(i) for i in positions]
            flags = tuple(c.pandas_dtype.kind in "mM" for c in cols)
            nas = isna_columns([c.raw for c in cols], flags, negate=False)

            if nas:
                from modin_tpu.ops.lazy import run_fused

                def keep_tail(arrs):
                    import jax.numpy as jnp

                    stacked = jnp.stack(arrs, axis=0)
                    bad = (
                        jnp.any(stacked, axis=0)
                        if how == "any"
                        else jnp.all(stacked, axis=0)
                    )
                    return ~bad

                keep_dev = run_fused(
                    nas, tail_key=("dropna_keep", how), tail_builder=keep_tail
                )
                if all(
                    (not c.is_device) or c.host_cache is not None
                    for c in frame._columns
                ):
                    # cached columns: host-positions path keeps the bit-exact
                    # host copies through the row drop
                    return type(self)(
                        frame.filter_rows_mask(np.asarray(keep_dev)),
                        self._shape_hint,
                    )
                return type(self)(
                    frame.filter_rows_mask_device(keep_dev), self._shape_hint
                )
            return type(self)(
                frame.filter_rows_mask(np.ones(len(frame), bool)),
                self._shape_hint,
            )
        return super().dropna(**kwargs)

    # --------------------------- value_counts -------------------------- #

    def series_value_counts(self, **kwargs: Any) -> "TpuQueryCompiler":
        normalize = kwargs.get("normalize", False)
        sort = kwargs.get("sort", True)
        ascending = kwargs.get("ascending", False)
        bins = kwargs.get("bins")
        dropna = kwargs.get("dropna", True)
        frame = self._modin_frame
        col = frame.get_column(0) if frame.num_cols == 1 else None
        decoder = None
        data_col = col
        if col is not None and not col.is_device and bins is None and len(frame) > 0:
            # string/object series count by their dictionary codes
            from modin_tpu.ops.dictionary import encode_host_column

            enc = encode_host_column(col)
            if enc is not None:
                data_col, decoder = enc.codes, enc.categories
        if (
            bins is None
            and data_col is not None
            and data_col.is_device
            and (decoder is not None or col.pandas_dtype.kind in "biuf")
            and len(frame) > 0
        ):
            from modin_tpu.ops import groupby as gb_ops

            try:
                codes, n_groups, group_keys, sizes = gb_ops.factorize_keys_cached(
                    [data_col.data], len(frame), dropna=dropna
                )
            except gb_ops._TooManyGroups:
                return super().series_value_counts(**kwargs)
            if n_groups == 0:
                return super().series_value_counts(**kwargs)
            import jax

            counts_dev = gb_ops.groupby_reduce(
                "size", [], codes, n_groups, len(frame), sizes=sizes
            )[0]
            first_dev = gb_ops.groupby_first_position(codes, n_groups)
            counts, first_pos = (
                np.asarray(v)
                for v in _engine_materialize((counts_dev, first_dev))
            )
            counts = counts[:n_groups]
            if decoder is not None:
                from modin_tpu.ops.dictionary import decode_codes

                keys = decode_codes(np.asarray(group_keys[0]), decoder)
            else:
                keys = np.asarray(group_keys[0])
            values = counts / counts.sum() if normalize else counts
            name = frame.columns[0]
            result = pandas.Series(
                values,
                index=pandas.Index(
                    keys, name=None if name == MODIN_UNNAMED_SERIES_LABEL else name
                ),
            )
            if sort:
                # pandas orders by count with ties in first-appearance order
                order = np.lexsort(
                    (first_pos, counts if ascending else -counts)
                )
            else:
                # sort=False preserves the data's first-appearance order
                order = np.argsort(first_pos, kind="stable")
            result = result.iloc[order]
            result.name = "proportion" if normalize else "count"
            qc = type(self).from_pandas(result.to_frame())
            qc._shape_hint = "column"
            return qc
        return super().series_value_counts(**kwargs)

    # ------------------------------ merge ----------------------------- #

    def merge(self, right: Any, **kwargs: Any) -> "TpuQueryCompiler":
        if graftstream.STREAM_ON and isinstance(right, TpuQueryCompiler):
            # graftstream: the residency router, not a flag, sends an
            # out-of-core join through the spill-aware external merge
            if _decide_windowed(
                "merge", (self._modin_frame, right._modin_frame)
            ):
                streamed = graftstream.external_merge_qc(self, right, kwargs)
                if streamed is not None:
                    return streamed
        result = self._try_device_merge(right, kwargs)
        if result is not None:
            return result
        return super().merge(right, **kwargs)

    @device_path("merge")
    def _try_device_merge(self, right: Any, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        from modin_tpu.ops.join import (
            composite_key_codes,
            gather_right_columns,
            merge_positions,
            right_only_positions,
        )
        from modin_tpu.ops.structural import gather_columns_device
        from modin_tpu.utils import hashable

        how = kwargs.get("how", "inner")
        if how not in ("inner", "left", "right", "outer"):
            return None
        if (
            kwargs.get("left_index")
            or kwargs.get("right_index")
            or kwargs.get("sort")
            or kwargs.get("indicator")
            or kwargs.get("validate") is not None
            or not isinstance(right, TpuQueryCompiler)
        ):
            return None

        # ---- resolve key label pairs (multi-key capable) ---------------- #
        on = kwargs.get("on")
        left_on = kwargs.get("left_on")
        right_on = kwargs.get("right_on")

        def as_list(x):
            return list(x) if isinstance(x, list) else [x]

        if on is not None:
            l_labels = r_labels = as_list(on)
        elif left_on is not None and right_on is not None:
            l_labels, r_labels = as_list(left_on), as_list(right_on)
            if len(l_labels) != len(r_labels):
                return None
        else:
            return None
        if not all(hashable(x) for x in l_labels + r_labels):
            return None  # array-like keys take the pandas fallback
        # pandas collapses a key pair with identical labels into one column
        coalesce = [ll == rl for ll, rl in zip(l_labels, r_labels)]

        lframe, rframe = self._modin_frame, right._modin_frame
        if not lframe.columns.is_unique or not rframe.columns.is_unique:
            return None
        lkey_positions, rkey_positions = [], []
        for ll, rl in zip(l_labels, r_labels):
            lp = lframe.column_position(ll)
            rp = rframe.column_position(rl)
            if len(lp) != 1 or lp[0] < 0 or len(rp) != 1 or rp[0] < 0:
                return None
            lkey_positions.append(lp[0])
            rkey_positions.append(rp[0])
        # dict_key_pairs[ki] = ((l_codes_col, l_cats), (r_codes_col, r_cats))
        # for string/object key pairs riding their dictionary encodings
        # (ops/dictionary.py): codes are remapped to the union dictionary
        # below and the numeric sort-merge join applies unchanged
        dict_key_pairs: dict = {}
        for ki, (lp, rp) in enumerate(zip(lkey_positions, rkey_positions)):
            lc, rc = lframe.get_column(lp), rframe.get_column(rp)
            if (
                lc.is_device and rc.is_device
                and lc.pandas_dtype.kind in "biuf"
                # exact dtype match: same-kind different-width keys (int32 vs
                # int64) would mix sides' data under one declared dtype in the
                # coalesced right/outer paths — pandas promotes, so fall back
                and lc.pandas_dtype == rc.pandas_dtype
            ):
                continue
            if not lc.is_device and not rc.is_device:
                from modin_tpu.ops.dictionary import encode_host_column

                l_enc = encode_host_column(lc)
                r_enc = encode_host_column(rc)
                if l_enc is not None and r_enc is not None:
                    dict_key_pairs[ki] = (l_enc, r_enc)
                    continue
            return None
        if len(lframe) == 0 or len(rframe) == 0:
            return None
        # host columns are allowed when object/str-typed: their output rows
        # gather on the host by the (once-fetched) join positions; other
        # extension dtypes keep the pandas fallback
        for fr in (lframe, rframe):
            for c in fr._columns:
                if c.is_category:
                    return None
                if not c.is_device and not (
                    pandas.api.types.is_object_dtype(c.pandas_dtype)
                    or isinstance(c.pandas_dtype, pandas.StringDtype)
                ):
                    return None
        suffixes = kwargs.get("suffixes") or ("_x", "_y")
        if (
            not isinstance(suffixes, (tuple, list))
            or len(suffixes) != 2
            or not all(isinstance(sfx, str) and sfx for sfx in suffixes)
        ):
            return None  # None/empty suffixes have pandas-specific semantics

        # the right key column disappears from the output for coalesced pairs
        coalesced_rkeys = {
            rp for rp, co in zip(rkey_positions, coalesce) if co
        }
        coalesced_lkeys = {
            lp for lp, co in zip(lkey_positions, coalesce) if co
        }
        lkey_to_rkey = {
            lp: rp for lp, rp, co in zip(lkey_positions, rkey_positions, coalesce) if co
        }
        if how == "outer" and not all(coalesce):
            # pandas sorts an outer result by the join key tuple; with
            # distinct left_on/right_on labels the key lives in two columns —
            # keep that shape on the pandas fallback
            return None
        right_value_positions = [
            i for i in range(rframe.num_cols) if i not in coalesced_rkeys
        ]
        # null-side bool columns become object dtype in pandas — fallback
        if how in ("left", "outer") and any(
            rframe.get_column(i).pandas_dtype.kind == "b"
            for i in right_value_positions
        ):
            return None
        if how in ("right", "outer") and any(
            lframe.get_column(i).pandas_dtype.kind == "b"
            for i in range(lframe.num_cols)
            if i not in coalesced_lkeys
        ):
            return None

        lframe.materialize_device()
        rframe.materialize_device()

        # ---- key codes -------------------------------------------------- #
        lkey_datas, rkey_datas = [], []
        for ki, (lp, rp) in enumerate(zip(lkey_positions, rkey_positions)):
            if ki in dict_key_pairs:
                from modin_tpu.ops.dictionary import (
                    remap_codes_device,
                    union_categories,
                )

                l_enc, r_enc = dict_key_pairs[ki]
                _, l_map, r_map = union_categories(
                    l_enc.categories, r_enc.categories
                )
                lkey_datas.append(remap_codes_device(l_enc.codes.data, l_map))
                rkey_datas.append(remap_codes_device(r_enc.codes.data, r_map))
            else:
                lkey_datas.append(lframe.get_column(lp).data)
                rkey_datas.append(rframe.get_column(rp).data)
        if len(lkey_positions) == 1:
            lkey, rkey = lkey_datas[0], rkey_datas[0]
        else:
            lkey, rkey = composite_key_codes(lkey_datas, rkey_datas)

        # ---- match positions -------------------------------------------- #
        if how == "right":
            # probe from the right side: output rows follow right order and
            # the left side is the nullable one
            rprobe_left, rprobe_right, n_out, has_miss = merge_positions(
                rkey, lkey, len(rframe), len(lframe), how="left"
            )
            left_pos, right_pos = rprobe_right, rprobe_left
        else:
            probe_how = "left" if how in ("left", "outer") else "inner"
            left_pos, right_pos, n_out, has_miss = merge_positions(
                lkey, rkey, len(lframe), len(rframe), how=probe_how
            )

        import jax.numpy as jnp

        # outer: right rows the left join missed get appended
        appendix_positions, n_appendix = None, 0
        if how == "outer":
            appendix_positions, n_appendix = right_only_positions(
                right_pos, rframe.get_column(0).data.shape[0], len(rframe),
                n_out,
            )
        left_has_nulls = (how == "right" and has_miss) or n_appendix > 0
        right_has_nulls = how in ("left", "outer") and has_miss
        n_total = n_out + n_appendix

        # ---- gather + assemble ------------------------------------------ #
        # host (object) columns gather on the host by the join positions,
        # fetched ONCE per positions array; device columns keep the fused
        # device gathers.  new_cols tuples: (data, dtype, src_i, side,
        # is_host) — host data is an UNPADDED length-n_out object array.
        import jax as _jax

        _pos_fetch_cache: dict = {}

        def _pos_h(arr, count):
            key_ = (id(arr), count)
            if key_ not in _pos_fetch_cache:
                _pos_fetch_cache[key_] = np.asarray(
                    _engine_materialize(arr)
                )[:count].astype(np.int64)
            return _pos_fetch_cache[key_]

        def _host_take(values, positions):
            vals = np.asarray(values, dtype=object)
            out = np.empty(len(positions), dtype=object)
            valid = positions >= 0
            out[valid] = vals[positions[valid]]
            if not valid.all():
                out[~valid] = np.nan
            return out

        def _restore_host_dtype(arr, dtype):
            # assembly works on plain object arrays; str-dtype (pandas>=3
            # default for strings) columns convert back at the end
            if pandas.api.types.is_object_dtype(dtype):
                return arr
            try:
                return pandas.array(arr, dtype=dtype)
            except (TypeError, ValueError):
                # join-introduced NaNs a strict extension dtype rejects:
                # keep the object array, matching pandas' merge upcasting
                return arr

        l_dev_positions = [
            i for i, c in enumerate(lframe._columns) if c.is_device
        ]
        if how == "right":
            l_gathered = gather_right_columns(
                [lframe._columns[i].data for i in l_dev_positions], left_pos
            )
        else:
            l_gathered = gather_columns_device(
                [lframe._columns[i].data for i in l_dev_positions], left_pos
            )
        l_data_by_pos = dict(zip(l_dev_positions, l_gathered))
        suffix_l, suffix_r = suffixes
        right_labels_set = {rframe.columns[i] for i in right_value_positions}
        new_cols: list = []
        new_labels: list = []
        key_appendix: dict = {}
        if n_appendix > 0:
            # appendix values for coalesced key columns come from the right key
            for lp, rp, co in zip(lkey_positions, rkey_positions, coalesce):
                if co:
                    key_appendix[lp] = rframe.get_column(rp)
        for i, col in enumerate(lframe._columns):
            label = lframe.columns[i]
            if label in right_labels_set and i not in coalesced_lkeys:
                label = f"{label}{suffix_l}"
            dtype = col.pandas_dtype
            if not col.is_device:
                if how == "right" and i in lkey_to_rkey:
                    # coalesced key in a right join: values come from the
                    # (always-valid) right side
                    data = _host_take(
                        rframe.get_column(lkey_to_rkey[i]).to_numpy(),
                        _pos_h(right_pos, n_out),
                    )
                else:
                    data = _host_take(col.to_numpy(), _pos_h(left_pos, n_out))
                new_cols.append((data, dtype, i, "left", True))
                new_labels.append(label)
                continue
            data = l_data_by_pos[i]
            if how == "right" and i in lkey_to_rkey:
                # coalesced key: every output row is a right row, so the key
                # value comes from the (always-valid) right side
                data = gather_columns_device(
                    [rframe.get_column(lkey_to_rkey[i]).data], right_pos
                )[0]
            if left_has_nulls and i not in coalesced_lkeys and dtype.kind in "iu":
                # pandas promotes int columns with missing matches to float64
                data = data.astype(jnp.float64)
                if how == "right":
                    data = jnp.where(left_pos < 0, jnp.nan, data)
                dtype = np.dtype(np.float64)
            new_cols.append((data, dtype, i, "left", False))
            new_labels.append(label)
        r_dev_positions = [
            i for i in right_value_positions if rframe.get_column(i).is_device
        ]
        right_datas = gather_right_columns(
            [rframe.get_column(i).data for i in r_dev_positions], right_pos
        )
        r_data_by_pos = dict(zip(r_dev_positions, right_datas))
        left_labels_set = set(lframe.columns)
        coalesced_label_set = {
            lframe.columns[lp] for lp in coalesced_lkeys
        }
        for i in right_value_positions:
            col = rframe.get_column(i)
            label = rframe.columns[i]
            if label in left_labels_set and label not in coalesced_label_set:
                label = f"{label}{suffix_r}"
            dtype = col.pandas_dtype
            if not col.is_device:
                data = _host_take(col.to_numpy(), _pos_h(right_pos, n_out))
                new_cols.append((data, dtype, i, "right", True))
                new_labels.append(label)
                continue
            data = r_data_by_pos[i]
            if right_has_nulls and dtype.kind in "iu":
                data = jnp.where(right_pos < 0, jnp.nan, data.astype(jnp.float64))
                dtype = np.dtype(np.float64)
            new_cols.append((data, dtype, i, "right", False))
            new_labels.append(label)

        if not pandas.Index(new_labels).is_unique:
            return None  # colliding suffixed labels: pandas raises MergeError

        # ---- outer appendix: right-only rows ----------------------------- #
        final_cols: list = []
        if n_appendix > 0:
            from modin_tpu.ops.join import _null_sentinel
            from modin_tpu.ops.structural import concat_columns

            app_pos_h = None
            dev_main, dev_appendix, dev_slots = [], [], []
            host_merged: dict = {}
            for slot, (data, dtype, src_i, side, is_host) in enumerate(new_cols):
                if is_host:
                    if app_pos_h is None:
                        app_pos_h = _pos_h(appendix_positions, n_appendix)
                    if side == "right":
                        app = _host_take(
                            rframe.get_column(src_i).to_numpy(), app_pos_h
                        )
                    elif src_i in key_appendix:
                        app = _host_take(
                            key_appendix[src_i].to_numpy(), app_pos_h
                        )
                    else:
                        app = np.full(n_appendix, np.nan, dtype=object)
                    host_merged[slot] = np.concatenate([data, app])
                    continue
                if side == "right":
                    app = gather_columns_device(
                        [rframe.get_column(src_i).data], appendix_positions
                    )[0]
                elif src_i in key_appendix:
                    app = gather_columns_device(
                        [key_appendix[src_i].data], appendix_positions
                    )[0]
                elif dtype.kind == "f":
                    app = jnp.full(appendix_positions.shape, jnp.nan, data.dtype)
                else:
                    app = jnp.full(
                        appendix_positions.shape,
                        _null_sentinel(data.dtype),
                        data.dtype,
                    )
                if app.dtype != data.dtype:
                    app = app.astype(data.dtype)
                dev_main.append(data)
                dev_appendix.append(app)
                dev_slots.append(slot)
            datas, _ = concat_columns(
                [dev_main, dev_appendix], [n_out, n_appendix]
            ) if dev_main else ([], None)
            dev_merged = dict(zip(dev_slots, datas))
            for slot, (data, dtype, _, _, is_host) in enumerate(new_cols):
                if is_host:
                    final_cols.append(
                        HostColumn(_restore_host_dtype(host_merged[slot], dtype))
                    )
                else:
                    final_cols.append(
                        DeviceColumn(dev_merged[slot], dtype, length=n_total)
                    )
        else:
            for data, dtype, _, _, is_host in new_cols:
                if is_host:
                    final_cols.append(HostColumn(_restore_host_dtype(data, dtype)))
                else:
                    final_cols.append(DeviceColumn(data, dtype, length=n_total))

        if how == "outer" and n_total > 0:
            # pandas always sorts an outer merge by the join keys (stable, so
            # within equal keys the left-join expansion order is kept).
            # Dict-encoded keys sort by their OUTPUT CODES (order-isomorphic
            # to the strings): codes gathered by the join positions + the
            # appendix, concatenated like the value columns were.
            from modin_tpu.ops import sort as sort_ops
            from modin_tpu.ops.structural import concat_columns

            key_arrays = []
            for ki, lp in enumerate(lkey_positions):
                if ki in dict_key_pairs:
                    main = gather_columns_device([lkey_datas[ki]], left_pos)[0]
                    if n_appendix > 0:
                        app = gather_columns_device(
                            [rkey_datas[ki]], appendix_positions
                        )[0]
                        merged, _ = concat_columns(
                            [[main], [app]], [n_out, n_appendix]
                        )
                        key_arrays.append(merged[0])
                    else:
                        key_arrays.append(main)
                else:
                    key_arrays.append(final_cols[lp].data)
            perm = sort_ops.lexsort_permutation(
                key_arrays, n_total, [True] * len(key_arrays)
            )
            perm_h = None
            sorted_dev = gather_columns_device(
                [c.data for c in final_cols if c.is_device], perm
            )
            di = iter(sorted_dev)
            resorted: list = []
            for c in final_cols:
                if c.is_device:
                    resorted.append(
                        DeviceColumn(next(di), c.pandas_dtype, length=n_total)
                    )
                else:
                    if perm_h is None:
                        perm_h = np.asarray(_engine_materialize(perm))[:n_total]
                    resorted.append(HostColumn(c.data[perm_h]))
            final_cols = resorted

        result_frame = TpuDataframe(
            final_cols,
            pandas.Index(new_labels),
            LazyIndex(pandas.RangeIndex(n_total), n_total),
            nrows=n_total,
        )
        return type(self)(result_frame)

    # ----------------------------- rolling ---------------------------- #

    @device_path("rolling")
    def _try_device_rolling(self, op: str, rolling_kwargs: dict, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        from modin_tpu.ops.window import rolling_reduce

        window = rolling_kwargs.get("window")
        if not isinstance(window, (int, np.integer)) or window <= 0:
            return None
        for key in ("center", "win_type", "on", "closed", "step"):
            if rolling_kwargs.get(key) not in (None, False):
                return None
        if rolling_kwargs.get("method", "single") != "single":
            return None
        extra = dict(kwargs)
        ddof = extra.pop("ddof", 1) if op in ("var", "std", "sem") else 1
        if extra.pop("numeric_only", False):
            return None  # changes column selection: pandas fallback
        if extra or not isinstance(ddof, (int, np.integer)):
            return None  # unknown kwargs (incl. ddof on sum/...): pandas raises
        min_periods = rolling_kwargs.get("min_periods")
        if min_periods is None:
            min_periods = int(window)  # pandas >= 2: count defaults like the rest
        elif not isinstance(min_periods, (int, np.integer)) or not (
            0 <= min_periods <= window
        ):
            return None  # pandas raises the proper ValueError on the fallback
        frame = self._modin_frame
        if len(frame) == 0 or not all(
            c.is_device and c.pandas_dtype.kind in "iuf" for c in frame._columns
        ):
            return None
        frame.materialize_device()
        datas = rolling_reduce(
            op, [c.data for c in frame._columns], len(frame), int(window),
            int(min_periods), int(ddof),
        )
        return self._wrap_device_result(datas)

    @staticmethod
    def _parse_ewm_kwargs(ewm_kwargs: dict):
        """Resolve ewm construction kwargs to (alpha, adjust, ignore_na,
        min_periods), or None when only the pandas fallback can honor (or
        properly reject) them."""
        ek = dict(ewm_kwargs)
        if ek.pop("times", None) is not None:
            return None
        if ek.pop("method", "single") != "single":
            return None
        com = ek.pop("com", None)
        span = ek.pop("span", None)
        halflife = ek.pop("halflife", None)
        alpha = ek.pop("alpha", None)
        adjust = ek.pop("adjust", True)
        ignore_na = ek.pop("ignore_na", False)
        min_periods = ek.pop("min_periods", 0)
        if ek:
            return None
        if min_periods is None:
            min_periods = 0
        if (
            isinstance(min_periods, bool)
            or not isinstance(min_periods, (int, np.integer))
            or min_periods < 0
        ):
            return None
        if not isinstance(adjust, (bool, np.bool_)) or not isinstance(
            ignore_na, (bool, np.bool_)
        ):
            return None
        decay = [v for v in (com, span, halflife, alpha) if v is not None]
        if len(decay) != 1 or isinstance(decay[0], bool) or not isinstance(
            decay[0], (int, float, np.integer, np.floating)
        ):
            # zero/multiple decay params or a timedelta halflife: pandas
            # raises the proper error on the fallback
            return None
        if com is not None:
            if com < 0:
                return None
            a = 1.0 / (1.0 + float(com))
        elif span is not None:
            if span < 1:
                return None
            a = 2.0 / (float(span) + 1.0)
        elif halflife is not None:
            if halflife <= 0:
                return None
            a = 1.0 - float(np.exp(-np.log(2.0) / float(halflife)))
        else:
            if not 0 < alpha <= 1:
                return None
            a = float(alpha)
        return a, bool(adjust), bool(ignore_na), int(min_periods)

    @device_path("ewm")
    def _try_device_ewm(self, op: str, ewm_kwargs: dict, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        """Exponentially weighted windows as associative linear-recurrence
        scans (ops/window.py ewm_reduce).  Reference surface:
        modin/pandas/window.py ExponentialMovingWindow (per-block pandas);
        times/method='table'/numeric_only and non-numeric frames fall back."""
        from modin_tpu.ops.window import ewm_reduce

        parsed = self._parse_ewm_kwargs(ewm_kwargs)
        if parsed is None:
            return None
        a, adjust, ignore_na, min_periods = parsed
        extra = dict(kwargs)
        bias = extra.pop("bias", False) if op in ("var", "std") else False
        if not isinstance(bias, (bool, np.bool_)):
            return None
        if extra.pop("numeric_only", False):
            return None  # changes column selection: pandas fallback
        for k in ("engine", "engine_kwargs"):
            if k in extra and extra[k] is None:
                extra.pop(k)
        if extra:
            return None
        if op == "sum" and not adjust:
            return None  # pandas raises NotImplementedError on the fallback
        frame = self._modin_frame
        if len(frame) == 0 or not all(
            c.is_device and c.pandas_dtype.kind in "iuf" for c in frame._columns
        ):
            return None
        frame.materialize_device()
        datas = ewm_reduce(
            op, [c.data for c in frame._columns], len(frame), a, bool(adjust),
            bool(ignore_na), int(min_periods), bool(bias),
        )
        return self._wrap_device_result(datas)

    @device_path("ewm")
    def _try_device_ewm_pair(
        self, op: str, ewm_kwargs: dict, kwargs: dict
    ) -> Optional["TpuQueryCompiler"]:
        """ewm cov/corr under JOINT validity (ops/window.py ewm_pair_reduce).

        Covered shapes: self vs itself (other=None) and self vs a
        label-matched same-length compiler (Series-vs-Series and
        column-matched frames).  pairwise=True's MultiIndex block output
        stays on the pandas fallback."""
        from modin_tpu.ops.window import ewm_pair_reduce

        parsed = self._parse_ewm_kwargs(ewm_kwargs)
        if parsed is None:
            return None
        a, adjust, ignore_na, min_periods = parsed
        extra = dict(kwargs)
        other = extra.pop("other", None)
        if extra.pop("pairwise", None) not in (None, False):
            return None
        bias = extra.pop("bias", False) if op == "cov" else False
        if not isinstance(bias, (bool, np.bool_)):
            return None
        if extra.pop("numeric_only", False):
            return None
        if extra:
            return None
        frame = self._modin_frame
        if len(frame) == 0 or not all(
            c.is_device and c.pandas_dtype.kind in "iuf" for c in frame._columns
        ):
            return None
        both_series = self._shape_hint == "column" and (
            other is None or getattr(other, "_shape_hint", None) == "column"
        )
        if other is None:
            if self._shape_hint != "column":
                # DataFrame cov/corr with no other is PAIRWISE in pandas
                # (MultiIndex block output) — fallback territory
                return None
            oframe = frame
        else:
            if not isinstance(other, TpuQueryCompiler):
                return None
            oframe = other._modin_frame
            if len(oframe) != len(frame) or not all(
                c.is_device and c.pandas_dtype.kind in "iuf"
                for c in oframe._columns
            ):
                return None
            if frame.num_cols != oframe.num_cols:
                return None
            # Series pairs ignore names; frames must be column-matched
            if not both_series and not frame.columns.equals(oframe.columns):
                return None
            if not self._fast_index_match(other) and not frame.index.equals(
                oframe.index
            ):
                # pandas aligns on labels first; misaligned inputs fall back
                return None
        frame.materialize_device()
        oframe.materialize_device()
        datas = ewm_pair_reduce(
            op,
            [c.data for c in frame._columns],
            [c.data for c in oframe._columns],
            len(frame), a, bool(adjust), bool(ignore_na), int(min_periods),
            bool(bias),
        )
        col_labels = None
        if (
            other is not None
            and both_series
            and frame.columns[0] != oframe.columns[0]
        ):
            # binary-op name convention: differing names -> unnamed
            col_labels = pandas.Index([MODIN_UNNAMED_SERIES_LABEL])
        return self._wrap_device_result(datas, col_labels=col_labels)

    def ewm_cov(self, ewm_kwargs: dict, *args: Any, **kwargs: Any):
        result = (
            self._try_device_ewm_pair("cov", ewm_kwargs, dict(kwargs))
            if not args
            else None
        )
        if result is not None:
            return result
        return super().ewm_cov(ewm_kwargs, *args, **kwargs)

    def ewm_corr(self, ewm_kwargs: dict, *args: Any, **kwargs: Any):
        result = (
            self._try_device_ewm_pair("corr", ewm_kwargs, dict(kwargs))
            if not args
            else None
        )
        if result is not None:
            return result
        return super().ewm_corr(ewm_kwargs, *args, **kwargs)

    @device_path("resample")
    def _try_device_resample(self, op: str, resample_kwargs: dict, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        """Fixed-frequency resample as time-bucket codes + segment aggregation.

        The reference runs pandas.resample per row block and regroups
        (ResampleDefault here, fold in the reference); on device the bucket
        id of every row comes from pandas' own binner over the (host-side)
        datetime index — every rule family (tick, calendar anchors ME/QE/YE/
        W/B, closed/label/origin/offset variants) — and the aggregation is
        the same segment kernel groupby uses; empty buckets fall out
        naturally (sum 0, count 0, mean/min/max NaN).  Non-monotonic or
        NaT-bearing indexes fall back.
        """
        from modin_tpu.ops import groupby as gb_ops
        from modin_tpu.ops.structural import pad_len
        from modin_tpu.parallel.engine import JaxWrapper

        rule = resample_kwargs.get("rule")
        defaults = {
            "convention": "start", "on": None, "level": None,
            "group_keys": False, "axis": 0,
        }
        for key, default in defaults.items():
            if resample_kwargs.get(key, default) != default:
                return None
        extra = dict(kwargs)
        ddof = extra.pop("ddof", 1) if op in ("var", "std") else 1
        if extra.pop("numeric_only", False):
            return None
        if extra or not isinstance(ddof, (int, np.integer)):
            return None
        frame = self._modin_frame
        if len(frame) == 0:
            return None
        index = frame.index
        if not isinstance(index, pandas.DatetimeIndex):
            return None
        if index.hasnans:
            return None  # pandas drops NaT rows before binning
        if not index.is_monotonic_increasing:
            # the cumulative-bin trick below requires sorted timestamps
            return None
        # pandas' own binner (every rule family: Tick, W/ME/QE/YE anchors,
        # business days; closed/label/origin/offset semantics included) —
        # bins are cumulative row counts per bucket over the sorted index
        try:
            grouper = pandas.Grouper(
                freq=rule,
                closed=resample_kwargs.get("closed"),
                label=resample_kwargs.get("label"),
                origin=resample_kwargs.get("origin", "start_day"),
                offset=resample_kwargs.get("offset"),
            )
            _binner, bins, bin_labels = grouper._get_time_bins(index)
        except (TypeError, ValueError):
            # rules/kwargs pandas' binner rejects (host-only work: device
            # failures can't occur inside _get_time_bins)
            return None
        n_groups = len(bin_labels)
        if n_groups == 0 or n_groups > (1 << 24):
            return None  # pathological rule vs span: huge empty range
        value_positions = [
            i for i, c in enumerate(frame._columns)
            if c.is_device and c.pandas_dtype.kind in "biuf"
        ]
        if op != "size" and (
            len(value_positions) != frame.num_cols or not value_positions
        ):
            return None

        # ---- bucket codes from the cumulative bins ---- #
        bucket_sizes = np.diff(np.r_[0, np.asarray(bins, dtype=np.int64)])
        codes_host = np.repeat(np.arange(n_groups, dtype=np.int64), bucket_sizes)
        has_empty = bool((bucket_sizes == 0).any())
        n = len(frame)
        if len(codes_host) != n:
            return None  # rows outside the binner (should not happen)
        codes_padded = np.full(pad_len(n), n_groups, dtype=np.int64)
        codes_padded[:n] = codes_host
        codes = JaxWrapper.put(codes_padded)

        import jax.numpy as jnp

        if op == "size":
            datas = gb_ops.groupby_reduce(
                "size", [], codes, n_groups, n, sizes=bucket_sizes
            )
            # a named series source keeps its name on the size result
            labels = (
                frame.columns[:1]
                if self._shape_hint == "column"
                else pandas.Index([MODIN_UNNAMED_SERIES_LABEL])
            )
            out_dtypes = [np.dtype(np.int64)]
        else:
            frame.materialize_device()
            arrays = []
            for i in value_positions:
                a = frame._columns[i].data
                if a.dtype == jnp.bool_:
                    if op in ("min", "max") and has_empty:
                        return None  # pandas yields object dtype here
                    if op in ("sum", "mean", "var", "std"):
                        a = a.astype(jnp.int64)
                if (
                    op in ("min", "max")
                    and has_empty
                    and jnp.issubdtype(a.dtype, jnp.integer)
                ):
                    # empty buckets put NaN in the result: pandas promotes
                    # int min/max to float64 exactly in this case
                    a = a.astype(jnp.float64)
                arrays.append(a)
            datas = gb_ops.groupby_reduce(
                op, arrays, codes, n_groups, n, ddof=int(ddof),
                sizes=bucket_sizes,
            )
            labels = frame.columns[value_positions]
            out_dtypes = [np.dtype(d.dtype) for d in datas]

        result_index = bin_labels  # pandas' own binner labels: exact parity
        new_cols = [
            DeviceColumn(d, dt, length=n_groups)
            for d, dt in zip(datas, out_dtypes)
        ]
        result_frame = TpuDataframe(new_cols, labels, result_index, nrows=n_groups)
        qc = type(self)(result_frame)
        if op == "size":
            qc._shape_hint = "column"
        return qc

    @device_path("expanding")
    def _try_device_expanding(self, op: str, expanding_args: list, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        from modin_tpu.ops.window import expanding_reduce

        min_periods = expanding_args[0] if expanding_args else 1
        method = expanding_args[1] if len(expanding_args) > 1 else "single"
        if method != "single":
            return None
        if not isinstance(min_periods, (int, np.integer)) or min_periods < 0:
            return None
        extra = dict(kwargs)
        ddof = extra.pop("ddof", 1) if op in ("var", "std", "sem") else 1
        if extra.pop("numeric_only", False):
            return None
        if extra or not isinstance(ddof, (int, np.integer)):
            return None  # unknown kwargs (incl. ddof on sum/...): pandas raises
        frame = self._modin_frame
        if len(frame) == 0 or not all(
            c.is_device and c.pandas_dtype.kind in "iuf" for c in frame._columns
        ):
            return None
        frame.materialize_device()
        datas = expanding_reduce(
            op, [c.data for c in frame._columns], len(frame),
            int(min_periods), int(ddof),
        )
        return self._wrap_device_result(datas)

    # ----------------------------- groupby ---------------------------- #

    def groupby_agg(
        self,
        by: Any,
        agg_func: Any,
        axis: int = 0,
        groupby_kwargs: Optional[dict] = None,
        agg_args: tuple = (),
        agg_kwargs: Optional[dict] = None,
        how: str = "axis_wise",
        drop: bool = False,
        series_groupby: bool = False,
        selection: Any = None,
    ) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.run_groupby_agg(
                self,
                by,
                agg_func,
                dict(
                    axis=axis,
                    groupby_kwargs=groupby_kwargs,
                    agg_args=agg_args,
                    agg_kwargs=agg_kwargs,
                    how=how,
                    drop=drop,
                    series_groupby=series_groupby,
                    selection=selection,
                ),
            )
            if planned is not None:
                return planned
        views_args = None
        if (
            graftview.VIEWS_ON
            and axis == 0
            and not agg_args
            and isinstance(agg_func, str)
        ):
            # graftview: a prior identical aggregation on these exact
            # buffers answers from the artifact registry — and an appended
            # frame folds only the tail rows through the device groupby
            from modin_tpu.views import groupby_cache

            views_args = (
                by, agg_func, groupby_kwargs or {}, agg_kwargs or {}, drop,
                series_groupby, selection,
            )
            try:
                cached = groupby_cache.groupby_consult(self, *views_args)
            except Exception:  # graftlint: disable=EXC-HYGIENE -- cache consult is best-effort: ANY failure (registry bug included) must degrade to the ordinary device path, never break the query
                cached = None
            if cached is not None:
                return cached
        result = self._try_device_groupby(
            by, agg_func, axis, groupby_kwargs or {}, agg_args, agg_kwargs or {},
            drop, series_groupby, selection,
        )
        if result is not None and views_args is not None:
            from modin_tpu.views import groupby_cache

            try:
                groupby_cache.groupby_record(self, result, *views_args)
            except Exception:  # graftlint: disable=EXC-HYGIENE -- cache recording is best-effort: the computed result is already correct and must be returned regardless
                pass
        if result is None:
            result = self._try_device_groupby_multi(
                by, agg_func, axis, groupby_kwargs or {}, agg_args,
                agg_kwargs or {}, drop, series_groupby, selection,
            )
        if result is None and not agg_args and axis == 0:
            from modin_tpu.ops.groupby import CUM_AGGS

            if (
                isinstance(agg_func, str)
                and agg_func in CUM_AGGS
                and not {
                    k: v for k, v in (agg_kwargs or {}).items()
                    if not (k == "numeric_only" and v is False)
                }
            ):
                result = self._try_device_groupby_cum(
                    agg_func, by, groupby_kwargs or {}, drop, series_groupby,
                    selection,
                )
        if (
            result is None
            and agg_func == "describe"
            and axis == 0
            and not agg_args
            and not series_groupby
        ):
            result = self._try_device_groupby_describe(
                by, groupby_kwargs or {}, agg_kwargs or {}, drop, selection
            )
        if result is None and callable(agg_func) and axis == 0 and not series_groupby:
            result = self._try_shuffle_groupby_apply(
                by, agg_func, groupby_kwargs or {}, agg_args, agg_kwargs or {},
                selection,
            )
        if result is not None:
            return result
        return super().groupby_agg(
            by, agg_func, axis=axis, groupby_kwargs=groupby_kwargs,
            agg_args=agg_args, agg_kwargs=agg_kwargs, how=how, drop=drop,
            series_groupby=series_groupby, selection=selection,
        )

    @device_path("groupby")
    def _try_device_groupby_describe(
        self, by, groupby_kwargs, agg_kwargs, drop, selection=None
    ) -> Optional["TpuQueryCompiler"]:
        """groupby.describe as a composition of eight device aggregations
        (count/mean/std/min/quantiles/max — every piece an existing segment
        or order kernel; the key factorization is memoized so the composite
        costs one factorize + eight kernels).  Reference defaults the whole
        thing to per-group pandas describe."""
        if (
            agg_kwargs.get("include") is not None
            or agg_kwargs.get("exclude") is not None
            or agg_kwargs.get("percentiles") is not None
        ):
            return None
        stats_plan = [
            ("count", {}),
            ("mean", {}),
            ("std", {}),
            ("min", {}),
            ("quantile", {"q": 0.25}),
            ("quantile", {"q": 0.5}),
            ("quantile", {"q": 0.75}),
            ("max", {}),
        ]
        parts = []
        for func, kw in stats_plan:
            r = self._try_device_groupby(
                by, func, 0, groupby_kwargs, (),
                {"numeric_only": True, **kw}, drop, False, selection,
            )
            if r is None:
                return None
            parts.append(r)
        stat_names = ["count", "mean", "std", "min", "25%", "50%", "75%", "max"]
        frames = [p._modin_frame for p in parts]
        vcols = list(frames[0].columns)
        if any(list(f.columns) != vcols for f in frames):
            return None
        import jax.numpy as jnp

        new_cols = []
        tuples = []
        f64 = np.dtype(np.float64)
        for vi, vc in enumerate(vcols):
            for si, st in enumerate(stat_names):
                col = frames[si].get_column(vi)
                if col.pandas_dtype != f64:
                    # pandas' describe emits a uniformly float64 frame
                    col = DeviceColumn(
                        col.data.astype(jnp.float64), f64, length=col.length
                    )
                new_cols.append(col)
                tuples.append((vc, st))
        result_frame = TpuDataframe(
            new_cols,
            pandas.MultiIndex.from_tuples(tuples),
            frames[0]._index,
            nrows=len(frames[0]),
        )
        return type(self)(result_frame)

    @device_path("shuffle_apply")
    def _try_shuffle_groupby_apply(
        self, by, agg_func, groupby_kwargs, agg_args, agg_kwargs, selection
    ) -> Optional["TpuQueryCompiler"]:
        """Non-reducible groupby UDFs through the range-partition shuffle.

        Reference: modin routes groupby.apply through
        ``_apply_func_to_range_partitioning`` + per-partition pandas apply
        (dataframe.py:4163, :2565).  TPU translation: range-partition the
        *row ids* by the key on device (parallel/shuffle.py) so every group
        lands wholly inside one shard range, then run the pandas UDF on each
        range's sub-frame fetched chunk-by-chunk and concatenate — host peak
        memory is O(chunk), never the full frame (the base-class path's
        ``self.to_pandas()`` cliff).
        """
        from modin_tpu.ops import groupby as gb_ops
        from modin_tpu.parallel.mesh import num_row_shards
        from modin_tpu.parallel.shuffle import ShuffleSkewError, range_shuffle

        S = num_row_shards()
        frame = self._modin_frame
        n = len(frame)
        if S < 2 or n < _SHUFFLE_APPLY_MIN_ROWS:
            return None
        if getattr(agg_func, "_row_shaped_groupby", False):
            # transform/filter results follow the ORIGINAL frame order; the
            # key-ordered chunk concat cannot reproduce that
            return None
        gk = dict(groupby_kwargs)
        if gk.get("level") is not None or gk.pop("axis", 0) not in (0, "index"):
            return None
        if gk.get("group_keys", True) is False:
            # with group_keys=False pandas restores original row order for
            # like-indexed UDF results — same concat-order hazard
            return None
        sort = gk.get("sort", True)
        as_index = gk.get("as_index", True)
        dropna = gk.get("dropna", True)
        if not sort and not dropna:
            # the appearance-order reorder maps result rows to groups by key
            # VALUE; NaN keys (kept by dropna=False) don't hash-match
            return None

        # ---- resolve keys: in-frame labels (numeric or dict-encoded) and
        #      external single-column compilers ---------------------------- #
        by_list = [by] if not isinstance(by, list) else list(by)
        key_datas = []
        key_decoders: List[Any] = []
        ext_positions: dict = {}
        for bi, b in enumerate(by_list):
            if isinstance(b, TpuQueryCompiler):
                eframe = b._modin_frame
                if (
                    eframe.num_cols != 1
                    or len(eframe) != n
                    or not self._fast_index_match(b)
                ):
                    return None
                col = eframe.get_column(0)
                ext_positions[bi] = b
            elif hasattr(b, "to_pandas"):
                return None
            else:
                pos = frame.column_position(b)
                if len(pos) != 1 or pos[0] < 0:
                    return None
                col = frame._columns[pos[0]]
            if col.is_device and col.pandas_dtype.kind in "biuf":
                if col.is_lazy:
                    # the OWNING frame batches the fused materialization —
                    # for an external by-Series that is eframe, not self
                    (eframe if bi in ext_positions else frame).materialize_device()
                key_datas.append(col.data)
                key_decoders.append(None)
            elif not col.is_device:
                from modin_tpu.ops.dictionary import encode_host_column

                enc = encode_host_column(col)
                if enc is None:
                    return None
                key_datas.append(enc.codes.data)
                key_decoders.append(enc.categories)
            else:
                return None

        # one composite group code per row: the shuffle key.  Sorted-group
        # codes keep chunk ranges in key order, so the chunk concat IS the
        # sort=True group order; NaN-key rows overflow past n_groups and the
        # in-chunk pandas groupby drops them (dropna=True)
        try:
            codes, n_groups, group_keys_u, _sizes = gb_ops.factorize_keys_cached(
                key_datas, n, dropna=dropna
            )
        except gb_ops._TooManyGroups:
            return None
        if n_groups == 0:
            return None
        codes = gb_ops.codes_array(codes)  # shuffled and indexed below, row by row

        import jax
        import jax.numpy as jnp

        iota = jnp.arange(codes.shape[0], dtype=jnp.int64)
        try:
            key_out, (rowid_out,), counts, _ = range_shuffle(codes, [iota], n)
        except ShuffleSkewError:
            return None
        rowids = np.asarray(rowid_out)[:n]
        # dropna=True gives NaN-key rows overflow codes; they must not reach
        # the chunks (an all-dropped chunk yields an empty apply result that
        # poisons the concat's index metadata)
        n_overflow = int(_engine_materialize(jnp.sum(codes[: n] >= n_groups)))
        if n_overflow:
            shuffled_codes = np.asarray(key_out)[:n]
            keep = shuffled_codes < n_groups
            new_counts = []
            start = 0
            kept_ids = []
            for count in counts:
                stop = start + int(count)
                seg = keep[start:stop]
                kept_ids.append(rowids[start:stop][seg])
                new_counts.append(int(seg.sum()))
                start = stop
            rowids = np.concatenate(kept_ids) if kept_ids else rowids[:0]
            counts = new_counts

        inner_gk = dict(groupby_kwargs)
        inner_gk["as_index"] = True
        inner_gk["sort"] = True
        results = []
        start = 0
        for count in counts:
            stop = start + int(count)
            if stop == start:
                start = stop
                continue
            chunk_ids = rowids[start:stop]
            sub = self.take_2d_positional(index=chunk_ids).to_pandas()
            by_arg = []
            for bi, b in enumerate(by_list):
                if bi in ext_positions:
                    ser = (
                        ext_positions[bi]
                        .take_2d_positional(index=chunk_ids)
                        .to_pandas()
                        .iloc[:, 0]
                    )
                    if ser.name == MODIN_UNNAMED_SERIES_LABEL:
                        ser.name = None
                    by_arg.append(ser)
                else:
                    by_arg.append(b)
            grp = sub.groupby(
                by=by_arg if len(by_arg) > 1 else by_arg[0], **inner_gk
            )
            if selection is not None:
                grp = grp[selection]
            results.append(agg_func(grp, *agg_args, **agg_kwargs))
            start = stop
        if not results:
            return None
        if not all(isinstance(r, (pandas.Series, pandas.DataFrame)) for r in results):
            return None
        nkeys = len(by_list)
        # Under group_keys=True every genuine Series/DataFrame UDF result
        # carries the key levels PREFIXED (nlevels >= nkeys+1), so a chunk
        # frame at exactly nkeys levels is pandas WIDENING Series results:
        # either (a) per-chunk, because the chunk held a single group of a
        # like-indexed UDF (columns = that group's row labels, differing per
        # chunk — stack back to the Series form the other chunks have), or
        # (b) globally, because the UDF returns a constant-index Series
        # (identical columns everywhere — pandas' own full-frame shape, so
        # the wide chunks concat as-is).  Without (a)'s restack, an
        # all-single-group chunking (n_groups <= shards) would concat
        # disjoint wide frames and silently corrupt.
        frames_at_k = [
            r
            for r in results
            if isinstance(r, pandas.DataFrame) and r.index.nlevels == nkeys
        ]
        if frames_at_k and not (
            len(frames_at_k) == len(results)
            and all(
                f.columns.equals(frames_at_k[0].columns) for f in frames_at_k
            )
        ):

            def _unwiden(r):
                # the row labels were the UDF series' index (level name None)
                # and the series' shared name rode into columns.name
                s = r.stack()
                s.index = s.index.set_names(
                    list(r.index.names) + [None]
                )
                s.name = r.columns.name
                return s

            results = [
                _unwiden(r)
                if isinstance(r, pandas.DataFrame) and r.index.nlevels == nkeys
                else r
                for r in results
            ]
        if len({type(r) for r in results}) > 1:
            return None
        result = pandas.concat(results)

        if not sort:
            # canonical result is key-sorted; pandas sort=False orders groups
            # by first appearance.  First row position per group comes from a
            # device segment-min; result rows reorder host-side by that rank.
            import jax

            first_pos = np.asarray(
                _engine_materialize(
                    jnp.full(n_groups, n, jnp.int64)
                    .at[jnp.where(iota < n, codes, n_groups)]
                    .min(jnp.minimum(iota, n), mode="drop")
                )
            )
            appearance = np.argsort(first_pos, kind="stable")
            rank_of_gid = np.empty(n_groups, dtype=np.int64)
            rank_of_gid[appearance] = np.arange(n_groups)
            from modin_tpu.ops.dictionary import decode_codes

            decoded_levels = [
                decode_codes(vals, cats) if cats is not None else vals
                for vals, cats in zip(group_keys_u, key_decoders)
            ]
            if nkeys == 1:
                gid_of_key = {k: g for g, k in enumerate(decoded_levels[0])}
                row_keys = result.index.get_level_values(0)
            else:
                gid_of_key = {
                    k: g for g, k in enumerate(zip(*decoded_levels))
                }
                row_keys = list(
                    zip(*[result.index.get_level_values(i) for i in range(nkeys)])
                )
            try:
                row_rank = np.fromiter(
                    (rank_of_gid[gid_of_key[k]] for k in row_keys),
                    dtype=np.int64,
                    count=len(result),
                )
            except KeyError:
                return None  # key value failed to round-trip: stay safe
            result = result.iloc[np.argsort(row_rank, kind="stable")]

        if not as_index:
            if isinstance(result, pandas.Series) and result.index.nlevels == nkeys:
                # scalar-per-group: keys become columns, value column named
                # None (pandas' exact shape for as_index=False apply)
                key_names = list(result.index.names)
                result = result.reset_index()
                # pandas names the value column the literal None (object
                # columns Index, "mixed" inferred type)
                result.columns = pandas.Index([*key_names, None], dtype=object)
            elif result.index.nlevels == nkeys:
                # widened constant-index-Series shape: keys become columns
                result = result.reset_index()
            else:
                result = result.droplevel(list(range(nkeys)))

        was_series = isinstance(result, pandas.Series)
        if was_series:
            name = (
                result.name if result.name is not None else MODIN_UNNAMED_SERIES_LABEL
            )
            result = result.to_frame(name)
        qc = self.from_pandas(result, type(frame))
        if was_series:
            qc._shape_hint = "column"
        return qc

    def groupby_transform(
        self,
        by: Any,
        agg_func: Any,
        groupby_kwargs: Optional[dict] = None,
        drop: bool = False,
        series_groupby: bool = False,
        selection: Any = None,
    ) -> "TpuQueryCompiler":
        result = self._try_device_groupby_transform(
            by, agg_func, groupby_kwargs or {}, drop, series_groupby, selection
        )
        if result is not None:
            return result
        return super().groupby_transform(
            by, agg_func, groupby_kwargs=groupby_kwargs, drop=drop,
            series_groupby=series_groupby, selection=selection,
        )

    @device_path("groupby")
    def _try_device_groupby_transform(
        self, by, agg_func, groupby_kwargs, drop, series_groupby, selection
    ) -> Optional["TpuQueryCompiler"]:
        """transform("sum"/"mean"/...) = segment aggregate + gather-back.

        Reference: groupby transform ships blocks to workers; here it is the
        memoized factorization, one segment kernel, and one row gather — the
        original frame shape and index are preserved.  Restricted to int/bool
        key columns (NaN keys make the output dtype data-dependent)."""
        from modin_tpu.ops import groupby as gb_ops

        if not isinstance(agg_func, str) or agg_func not in (
            gb_ops.SEGMENT_AGGS - {"size"}
        ):
            return None
        resolved = self._resolve_rowwise_groupby(
            by, groupby_kwargs, drop, selection, "biuf"
        )
        if resolved is None:
            return None
        value_positions, codes, n_groups, sizes = resolved
        frame = self._modin_frame
        import jax.numpy as jnp

        arrays = []
        for i in value_positions:
            a = frame._columns[i].data
            if a.dtype == jnp.bool_ and agg_func in ("sum", "prod", "mean", "var", "std", "sem"):
                a = a.astype(jnp.int64)
            arrays.append(a)
        aggs = gb_ops.groupby_reduce(
            agg_func, arrays, codes, n_groups, len(frame), sizes=sizes
        )
        datas = gb_ops.groupby_broadcast(aggs, codes)
        new_cols = [
            DeviceColumn(d, np.dtype(d.dtype), length=len(frame))
            for d in datas
        ]
        result_frame = TpuDataframe(
            new_cols,
            frame.columns[value_positions],
            frame._index,
            nrows=len(frame),
        )
        qc = type(self)(result_frame)
        if series_groupby:
            qc._shape_hint = "column"
        return qc

    def _resolve_rowwise_groupby(
        self, by, groupby_kwargs, drop, selection, value_kinds: str
    ):
        """Shared gate/resolution for row-shaped groupby ops (transform,
        cumulatives): returns (value_positions, codes, n_groups) or None.

        Restricted to int/bool key columns — NaN keys would make the output
        dtype (and NaN placement) data-dependent."""
        from modin_tpu.ops import groupby as gb_ops

        if groupby_kwargs.get("level") is not None:
            return None
        if groupby_kwargs.get("dropna", True) is not True:
            return None
        frame = self._modin_frame
        if len(frame) == 0:
            return None
        if not (isinstance(by, list) and drop and all(
            not hasattr(b, "to_pandas") for b in by
        )):
            return None
        key_positions = []
        for label in by:
            pos = frame.column_position(label)
            if len(pos) != 1 or pos[0] < 0:
                return None
            key_positions.append(pos[0])
        key_cols = [frame._columns[p] for p in key_positions]
        if not all(c.is_device and c.pandas_dtype.kind in "biu" for c in key_cols):
            return None

        if selection is not None:
            sel_list = [selection] if not isinstance(selection, list) else list(selection)
            value_positions = []
            for label in sel_list:
                pos = frame.column_position(label)
                if len(pos) != 1 or pos[0] < 0:
                    return None
                value_positions.append(pos[0])
        else:
            value_positions = [
                i for i in range(frame.num_cols) if i not in key_positions
            ]
        value_cols = [frame._columns[i] for i in value_positions]
        if not value_cols or not all(
            c.is_device and c.pandas_dtype.kind in value_kinds
            for c in value_cols
        ):
            return None

        frame.materialize_device()
        try:
            codes, n_groups, _keys, sizes = gb_ops.factorize_keys_cached(
                [c.data for c in key_cols], len(frame)
            )
        except gb_ops._TooManyGroups:
            return None
        if n_groups == 0:
            return None
        return value_positions, codes, n_groups, sizes

    @device_path("groupby")
    def _try_device_groupby_cum(
        self, op, by, groupby_kwargs, drop, series_groupby, selection
    ) -> Optional["TpuQueryCompiler"]:
        """Row-shaped grouped cumulatives: ONE segmented scan over rows
        sorted by group code, scattered back to original row order."""
        from modin_tpu.ops import groupby as gb_ops

        # bools change dtype per-op in pandas: value kinds exclude them
        resolved = self._resolve_rowwise_groupby(
            by, groupby_kwargs, drop, selection, "iuf"
        )
        if resolved is None:
            return None
        value_positions, codes, _n_groups, _sizes = resolved
        frame = self._modin_frame
        import jax.numpy as jnp

        arrays = []
        for i in value_positions:
            a = frame._columns[i].data
            if (
                op in ("cumsum", "cumprod")
                and jnp.issubdtype(a.dtype, jnp.signedinteger)
                and a.dtype != jnp.int64
            ):
                # pandas 3 promotes signed sub-int64 cumsum/cumprod to int64
                a = a.astype(jnp.int64)
            arrays.append(a)
        datas = gb_ops.groupby_cumulative(op, arrays, codes)
        new_cols = [
            DeviceColumn(d, np.dtype(d.dtype), length=len(frame)) for d in datas
        ]
        result_frame = TpuDataframe(
            new_cols,
            frame.columns[value_positions],
            frame._index,
            nrows=len(frame),
        )
        qc = type(self)(result_frame)
        if series_groupby:
            qc._shape_hint = "column"
        return qc

    @device_path("groupby")
    def _try_device_groupby_multi(
        self, by, agg_func, axis, groupby_kwargs, agg_args, agg_kwargs, drop,
        series_groupby, selection,
    ) -> Optional["TpuQueryCompiler"]:
        """agg(["sum", "mean"]) / agg({"col": "sum"}) / agg({"col": ["median",
        "std"]}) on device: one factorization (memoized), one kernel per
        aggregation, columns combined like pandas (MultiIndex (col, agg) for
        lists and for a dict with a list among its values, in the dict's and
        the lists' order; flat for a dict of strings).  The factorize cache
        makes the per-agg passes cheap.

        ``sort=False`` and ``as_index=False`` (the H2O script's phrasing) are
        honoured once for the combined answer by :meth:`_assemble_groupby`:
        the aggregations' key-sorted rows are gathered into order of first
        appearance, and the key columns go in front, resident, under a
        ``RangeIndex`` (a list ``agg`` pads their labels to ``(key, "")``)."""

        def run_one(func, sel):
            return self._groupby_parts(
                by, func, axis, groupby_kwargs, agg_args, agg_kwargs, drop, sel,
            )

        if (
            isinstance(agg_func, list)
            and agg_func
            and all(isinstance(f, str) for f in agg_func)
        ):
            if not series_groupby and len(set(agg_func)) != len(agg_func):
                return None  # pandas raises SpecificationError on duplicates
            parts = []
            for f in agg_func:
                part = run_one(f, selection)
                if part is None:
                    return None  # bail before running the remaining kernels
                parts.append(part)
            base_labels = parts[0].value_labels
            if isinstance(base_labels, pandas.MultiIndex):
                return None  # pandas flattens to a deeper MultiIndex
            if not all(p.value_labels.equals(base_labels) for p in parts[1:]):
                return None
            if series_groupby:
                # a series groupby yields flat agg-named columns
                picks = [(p, 0) for p in parts]
                new_labels = pandas.Index(list(agg_func))
            else:
                picks = [
                    (p, pos) for pos in range(len(base_labels)) for p in parts
                ]
                new_labels = pandas.MultiIndex.from_tuples(
                    [
                        (label, fname)
                        for label in base_labels
                        for fname in agg_func
                    ]
                )
        elif (
            isinstance(agg_func, dict)
            and agg_func
            and not series_groupby
            and selection is None
            and all(_named_aggs(f) for f in agg_func.values())
        ):
            # a list among the values labels every column (column, agg), as
            # pandas does, a string value included
            nested = any(isinstance(f, list) for f in agg_func.values())
            picks, labels = [], []
            for col, f in agg_func.items():
                for fname in f if isinstance(f, list) else [f]:
                    part = run_one(fname, [col])
                    if part is None:
                        return None
                    if len(part.datas) != 1:
                        return None
                    picks.append((part, 0))
                    labels.append((col, fname) if nested else col)
            new_labels = (
                pandas.MultiIndex.from_tuples(labels) if nested else pandas.Index(labels)
            )
        else:
            return None
        combined = picks[0][0]._replace(
            datas=[p.datas[i] for p, i in picks],
            out_dtypes=[p.out_dtypes[i] for p, i in picks],
            value_decoders=[p.value_decoders[i] for p, i in picks],
            value_labels=new_labels,
        )
        return self._assemble_groupby(combined, groupby_kwargs)

    @device_path("groupby")
    def _try_device_groupby(
        self, by, agg_func, axis, groupby_kwargs, agg_args, agg_kwargs, drop,
        series_groupby, selection,
    ) -> Optional["TpuQueryCompiler"]:
        """One named aggregation on the device, or None (pandas answers).

        Keys: numeric device columns, resident ``category`` columns (their
        codes are the key, ``factorize_keys``' ``code_widths``), and host
        string / object columns through their dictionary codes.  ``dropna``
        and ``observed=True`` are the factorisation's; ``sort=False`` and
        ``as_index=False`` are honoured by :meth:`_assemble_groupby`."""
        parts = self._groupby_parts(
            by, agg_func, axis, groupby_kwargs, agg_args, agg_kwargs, drop,
            selection,
        )
        if parts is None:
            return None
        qc = self._assemble_groupby(parts, groupby_kwargs)
        if qc is not None and (series_groupby or agg_func == "size"):
            qc._shape_hint = "column"
        return qc

    def _groupby_parts(
        self, by, agg_func, axis, groupby_kwargs, agg_args, agg_kwargs, drop,
        selection,
    ) -> Optional["_GroupbyParts"]:
        """The kernels' answer to one named aggregation: a device column a
        value column, rows in key order (``factorize_keys``' codes), with the
        factorisation beside it — or None where the device declines."""
        from modin_tpu.ops import groupby as gb_ops

        if axis != 0 or agg_args:
            return None
        if not isinstance(agg_func, str) or agg_func not in (
            gb_ops.SEGMENT_AGGS | gb_ops.ORDER_AGGS
        ):
            return None
        if groupby_kwargs.get("level") is not None:
            return None
        if not groupby_kwargs.get("as_index", True) and agg_func == "size":
            return None
        dropna = groupby_kwargs.get("dropna", True)
        # gate agg kwargs
        numeric_only = bool(agg_kwargs.get("numeric_only", False))
        if agg_kwargs.get("min_count", 0) not in (0, -1):
            return None
        if agg_kwargs.get("skipna", True) is not True:
            return None
        ddof = int(agg_kwargs.get("ddof", 1))
        extra = set(agg_kwargs) - {
            "numeric_only", "min_count", "ddof", "skipna", "engine",
            "engine_kwargs", "q", "interpolation", "dropna",
        }
        if extra:
            return None
        if agg_kwargs.get("engine") not in (None, "cython"):
            return None
        # order-statistic agg parameters
        if agg_func == "quantile":
            qval = agg_kwargs.get("q", 0.5)
            if not isinstance(qval, (int, float, np.integer, np.floating)):
                return None  # list-of-q builds a MultiIndex result: fall back
            if not (0 <= float(qval) <= 1):
                return None  # pandas raises "Each 'q' must be between 0 and 1"
            interp = agg_kwargs.get("interpolation", "linear")
            if interp not in ("linear", "lower", "higher", "midpoint", "nearest"):
                return None
        elif "q" in agg_kwargs or "interpolation" in agg_kwargs:
            return None
        else:
            qval, interp = 0.5, "linear"
        if agg_func == "nunique":
            values_dropna = bool(agg_kwargs.get("dropna", True))
        elif "dropna" in agg_kwargs:
            return None
        else:
            values_dropna = True

        frame = self._modin_frame

        # resolve key columns
        key_positions: List[int] = []
        key_labels: List[Any] = []
        external_key = None
        if isinstance(by, list) and drop and all(not hasattr(b, "to_pandas") for b in by):
            for label in by:
                pos = frame.column_position(label)
                if len(pos) != 1 or pos[0] < 0:
                    return None
                key_positions.append(pos[0])
                key_labels.append(label)
            key_cols = [frame._columns[p] for p in key_positions]
        elif isinstance(by, TpuQueryCompiler) or (
            isinstance(by, list) and len(by) == 1 and isinstance(by[0], TpuQueryCompiler)
        ):
            ext = by if isinstance(by, TpuQueryCompiler) else by[0]
            eframe = ext._modin_frame
            # non-device (object) external keys pass through: the key
            # resolution below dictionary-encodes them or falls back
            if eframe.num_cols != 1:
                return None
            if len(eframe) != len(frame) or not self._fast_index_match(ext):
                return None
            external_key = eframe.get_column(0)
            label = eframe.columns[0]
            key_labels.append(None if label == MODIN_UNNAMED_SERIES_LABEL else label)
            key_cols = [external_key]
        else:
            return None
        # device-computable keys: numeric device columns directly, host
        # string/object columns through their dictionary encoding (codes on
        # device, categories host-side — ops/dictionary.py); key_decoders[i]
        # holds the categories needed to translate level i's group codes
        # back to labels when building the result index
        key_data_cols = []
        key_decoders: List[Any] = []
        code_widths: List[Optional[int]] = []
        for ki, c in enumerate(key_cols):
            if isinstance(c.pandas_dtype, pandas.CategoricalDtype):
                # a category key: its codes are the key and their range comes
                # from the dtype.  A host column becomes resident here, once
                # (pandas' own codes uploaded as they are), and the frame
                # holds the resident column from now on: no codes travel in a
                # later request
                from modin_tpu.ops.dictionary import resident_category_column

                resident = resident_category_column(c)
                if resident is None:
                    return None
                if resident is not c:
                    if external_key is not None:
                        eframe._columns[0] = resident
                    else:
                        frame._columns[key_positions[ki]] = resident
                key_data_cols.append(resident)
                key_decoders.append(("cat", c.pandas_dtype))
                code_widths.append(len(c.pandas_dtype.categories))
                continue
            code_widths.append(None)
            if c.is_device and c.pandas_dtype.kind in "biuf":
                key_data_cols.append(c)
                key_decoders.append(None)
                continue
            if not c.is_device:
                from modin_tpu.ops.dictionary import encode_host_column

                enc = encode_host_column(c)
                if enc is not None:
                    key_data_cols.append(enc.codes)
                    key_decoders.append(enc.categories)
                    continue
            return None
        if len(frame) == 0:
            return None
        category_keys = any(w is not None for w in code_widths)
        if category_keys and not groupby_kwargs.get("observed", True):
            # observed=False keeps UNOBSERVED categories in the result; the
            # factorize only sees observed codes.  Take the device path only
            # when there is nothing unobserved (single categorical key and a
            # full category set) — the check runs after factorize below.
            if len(key_cols) > 1:
                return None

        # resolve value columns
        if selection is not None:
            sel_list = [selection] if not isinstance(selection, list) else list(selection)
            value_positions = []
            for label in sel_list:
                pos = frame.column_position(label)
                if len(pos) != 1 or pos[0] < 0:
                    return None
                value_positions.append(pos[0])
        else:
            value_positions = [
                i for i in range(frame.num_cols) if i not in key_positions
            ]
        # string/object VALUE columns participate through their dictionary
        # codes for the order/equality-shaped aggregations (sorted categories
        # make code min/max the lexicographic min/max; count/nunique/first/
        # last are code-agnostic); value_decoders[j] holds (categories,
        # source dtype) for columns whose per-group results decode back
        _DICT_VALUE_AGGS = ("min", "max", "first", "last", "count", "nunique")
        value_cols = []
        value_labels = []
        value_decoders: List[Any] = []
        for i in value_positions:
            col = frame._columns[i]
            # NOTE: datetime device columns are excluded — NaT is the int64-min
            # sentinel and would aggregate as a regular value
            if col.is_device and col.pandas_dtype.kind in "biuf":
                value_cols.append(col)
                value_labels.append(frame.columns[i])
                value_decoders.append(None)
                continue
            if numeric_only:
                from pandas.api.types import is_numeric_dtype

                if is_numeric_dtype(col.pandas_dtype):
                    return None  # numeric but not device-computable: fall back
                continue  # genuinely non-numeric: pandas would drop it too
            if (
                not col.is_device
                and agg_func in _DICT_VALUE_AGGS
                and not isinstance(col.pandas_dtype, pandas.CategoricalDtype)
            ):
                from modin_tpu.ops.dictionary import encode_host_column

                enc = encode_host_column(col)
                # empty categories = all-missing column; pandas' None-vs-nan
                # quirks there stay with the fallback
                if enc is not None and len(enc.categories):
                    value_cols.append(enc.codes)
                    value_labels.append(frame.columns[i])
                    value_decoders.append((enc.categories, col.pandas_dtype))
                    continue
            if agg_func == "size":
                continue
            return None
        if agg_func != "size" and not value_cols:
            return None

        frame.materialize_device()
        try:
            codes, n_groups, group_keys, sizes = gb_ops.factorize_keys_cached(
                [c.data for c in key_data_cols], len(frame), dropna=dropna,
                code_widths=code_widths if category_keys else None,
            )
        except gb_ops._TooManyGroups:
            return None
        if n_groups == 0:
            return None
        if category_keys and not groupby_kwargs.get("observed", True):
            keys0 = np.asarray(group_keys[0], dtype=np.float64)
            observed_cats = int(np.sum(~(np.isnan(keys0) | (keys0 < 0))))
            if observed_cats < len(key_cols[0].pandas_dtype.categories):
                return None  # unobserved categories: pandas keeps them

        # bool value columns aggregate as ints for sum/mean/... like pandas
        import jax.numpy as jnp

        arrays = []
        out_dtypes = []
        for c in value_cols:
            a = c.data
            if a.dtype == jnp.bool_:
                if agg_func == "quantile":
                    return None  # pandas: "Cannot use quantile with bool dtype"
                if agg_func in (
                    "sum", "prod", "mean", "var", "std", "sem", "median"
                ):
                    a = a.astype(jnp.int64)
            arrays.append(a)
        if agg_func == "size":
            datas = gb_ops.groupby_reduce(
                "size", [], codes, n_groups, len(frame), sizes=sizes
            )
            value_labels = [MODIN_UNNAMED_SERIES_LABEL]
            out_dtypes = [np.dtype(np.int64)]
        elif agg_func in ("median", "quantile"):
            datas = gb_ops.groupby_quantile(
                arrays, codes, n_groups, len(frame),
                q=float(qval), interpolation=interp,
                preserve_float_dtype=(agg_func == "median"),
            )
            # lower/higher/nearest keep the integer dtype (pandas semantics)
            out_dtypes = [np.dtype(d.dtype) for d in datas]
        elif agg_func == "nunique":
            datas = gb_ops.groupby_nunique(
                arrays, codes, n_groups, len(frame), dropna=values_dropna
            )
            out_dtypes = [np.dtype(np.int64)] * len(datas)
        elif agg_func in ("first", "last"):
            datas = gb_ops.groupby_first_last(
                agg_func, arrays, codes, n_groups, len(frame)
            )
            out_dtypes = [np.dtype(d.dtype) for d in datas]
        else:
            datas = gb_ops.groupby_reduce(
                agg_func, arrays, codes, n_groups, len(frame), ddof=ddof,
                sizes=sizes,
            )
            for c, d in zip(value_cols, datas):
                if c.pandas_dtype.kind in "mM" and agg_func in ("min", "max"):
                    out_dtypes.append(c.pandas_dtype)
                else:
                    out_dtypes.append(np.dtype(d.dtype))

        if agg_func == "size":
            value_decoders = [None]
        return _GroupbyParts(
            datas=list(datas),
            out_dtypes=out_dtypes,
            value_labels=pandas.Index(value_labels),
            value_decoders=[
                dec if dec is not None and agg_func in ("min", "max", "first", "last") else None
                for dec in value_decoders
            ],
            codes=codes,
            n_groups=n_groups,
            group_keys=group_keys,
            key_labels=key_labels,
            key_decoders=key_decoders,
            key_dtypes=[c.pandas_dtype for c in key_cols],
        )

    def _assemble_groupby(
        self, parts: "_GroupbyParts", groupby_kwargs: dict
    ) -> Optional["TpuQueryCompiler"]:
        """The frame of a device groupby's answer, as the caller phrased it.

        ``sort=False``: the kernels' key-sorted rows are gathered into order of
        first appearance (``ops/groupby.py`` ``groupby_first_seen``: G rows a
        column, on the device).  ``as_index=False`` with keys the device holds
        (numeric columns and resident ``category`` columns): the key columns go
        in front, in ``by``'s order, each resident and of the key's own dtype
        (a category key: the group's codes + the table's ``CategoricalDtype``),
        under a ``RangeIndex``; no label is built on the host.  Any other
        ``as_index=False`` goes through ``reset_index`` of the labelled answer
        as before."""
        from modin_tpu.ops import groupby as gb_ops
        from modin_tpu.ops.dictionary import decode_codes

        n_groups = parts.n_groups
        value_labels = parts.value_labels
        as_index = groupby_kwargs.get("as_index", True)
        datas = list(parts.datas)
        # a list agg's (column, agg) labels: key labels are padded to tuples
        pad = (
            ("",) * (value_labels.nlevels - 1)
            if isinstance(value_labels, pandas.MultiIndex)
            else None
        )
        key_labels = [
            lab if pad is None else (lab,) + pad for lab in parts.key_labels
        ]
        if not as_index and any(lab in value_labels for lab in key_labels):
            return None  # a key that is a value column too: pandas' own rules
        # numeric keys, and resident category keys (their group keys are codes)
        keys_as_columns = (
            not as_index
            and all(
                dec is None
                or (dec[0] == "cat" and np.asarray(keys).dtype.kind == "i")
                for dec, keys in zip(parts.key_decoders, parts.group_keys)
            )
            and all(lab is not None for lab in parts.key_labels)
        )
        order = None
        if not groupby_kwargs.get("sort", True) and n_groups > 1:
            order = gb_ops.groupby_first_seen(parts.codes, n_groups)

        key_cols: list = []
        if keys_as_columns:
            with _spans.span(
                "qc.groupby.assemble", layer="GROUPBY-ASSEMBLE",
                n_keys=len(key_labels), num_groups=n_groups,
            ):
                single = len(parts.group_keys) == 1
                keys = [
                    gb_ops.group_keys_device(g, single, order)
                    for g in parts.group_keys
                ]
                # what is not in the order yet: uploaded key tables, the values
                moved = iter(
                    gb_ops.groupby_take_groups(
                        [d for d, in_order in keys if not in_order] + datas, order
                    )
                    if order is not None
                    else datas
                )
                key_datas = [d if in_order else next(moved) for d, in_order in keys]
                datas = list(moved)
                key_cols = [
                    DeviceColumn(d, dt, length=n_groups)
                    for d, dt in zip(key_datas, parts.key_dtypes)
                ]
                result_index = pandas.RangeIndex(n_groups)
        else:
            if order is not None:
                datas = gb_ops.groupby_take_groups(datas, order)
            # build result index from group keys (dict-encoded levels translate
            # their code values back to labels; categorical levels rebuild their
            # dtype so the result gets a CategoricalIndex like pandas)
            decoded_keys = []
            for vals, dec in zip(parts.group_keys, parts.key_decoders):
                if dec is None:
                    decoded_keys.append(vals)
                elif isinstance(dec, tuple) and dec[0] == "cat":
                    vals = np.asarray(vals, dtype=np.float64)
                    int_codes = np.where(np.isnan(vals), -1, vals).astype(np.int64)
                    decoded_keys.append(
                        pandas.Categorical.from_codes(int_codes, dtype=dec[1])
                    )
                else:
                    decoded_keys.append(decode_codes(vals, dec))
            if len(parts.key_labels) == 1:
                result_index = pandas.Index(decoded_keys[0], name=parts.key_labels[0])
            else:
                result_index = pandas.MultiIndex.from_arrays(
                    decoded_keys, names=parts.key_labels
                )
            if order is not None:
                result_index = result_index.take(
                    np.asarray(_engine_materialize(order))[:n_groups]
                )

        new_cols: list = list(key_cols)
        for d, dt, dec in zip(datas, parts.out_dtypes, parts.value_decoders):
            if dec is not None:
                # dict value column: the per-group result is a CODE — decode
                # to labels (host, n_groups values) with the source dtype
                cats, src_dtype = dec
                decoded = decode_codes(
                    np.asarray(_engine_materialize(d))[:n_groups], cats
                )
                if isinstance(src_dtype, pandas.StringDtype):
                    decoded = pandas.array(decoded, dtype=src_dtype)
                new_cols.append(HostColumn(decoded))
            else:
                new_cols.append(DeviceColumn(d, dt, length=n_groups))
        labels = value_labels
        if key_cols:
            labels = pandas.Index(key_labels + list(value_labels), tupleize_cols=False)
            if pad is not None:
                labels = pandas.MultiIndex.from_tuples(list(labels))
        result_frame = TpuDataframe(new_cols, labels, result_index, nrows=n_groups)
        qc = type(self)(result_frame)
        if not as_index and not key_cols:
            # keys become regular columns with a RangeIndex
            qc = qc.reset_index(drop=False)
        return qc

    # ------------------------------- sort ----------------------------- #

    @device_path("sort_shuffle")
    def _try_range_partition_sort(self, columns: Any, ascending: Any, kwargs: dict) -> Optional["TpuQueryCompiler"]:
        """Explicit sample->pivots->all_to_all shuffle sort (RangePartitioning).

        Reference analogue: range-partitioning sort_by (dataframe.py:2742 +
        partition_manager.py:1937).  Taken when the RangePartitioning config
        opts in, OR — graftmesh — when the kernel router's calibrated
        crossover predicts the collective sort beats the global argsort at
        this (rows, mesh shape): the router, not a flag, decides when
        collectives pay.
        """
        from modin_tpu.config import RangePartitioning
        from modin_tpu.parallel.mesh import num_row_shards
        from modin_tpu.parallel.shuffle import ShuffleSkewError, range_shuffle

        if num_row_shards() < 2:
            return None
        if kwargs.get("na_position", "last") != "last" or kwargs.get("key") is not None:
            return None
        col_list = [columns] if not isinstance(columns, list) else list(columns)
        if len(col_list) != 1:
            return None
        asc = ascending if not isinstance(ascending, list) else ascending[0]
        frame = self._modin_frame
        pos = frame.column_position(col_list[0])
        if len(pos) != 1 or pos[0] < 0:
            return None
        key_col = frame._columns[pos[0]]
        if not key_col.is_device or key_col.pandas_dtype.kind not in "biuf":
            return None
        if not all(c.is_device for c in frame._columns) or len(frame) == 0:
            return None
        if not RangePartitioning.get():
            from modin_tpu.ops import router

            # payload = the row-id column + every non-key column, all moved
            # through the all_to_all the local argsort path never pays
            if (
                router.decide_layout(
                    "sort", len(frame), payload_cols=frame.num_cols
                )
                != "sharded"
            ):
                return None
        import jax.numpy as jnp

        frame.materialize_device()
        n = len(frame)
        iota = jnp.arange(key_col.data.shape[0], dtype=jnp.int64)
        other_cols = [c.data for i, c in enumerate(frame._columns) if i != pos[0]]
        try:
            key_out, cols_out, counts, _ = range_shuffle(
                key_col.data, [iota] + other_cols, n, descending=not asc, local_sort=True
            )
        except ShuffleSkewError:
            # Pathological key skew exhausted the capacity-slack retries (all
            # rows landing on one shard); the global argsort path below
            # handles any distribution.
            return None
        perm_out = cols_out[0]
        rest = cols_out[1:]
        new_cols: list = [None] * frame.num_cols
        new_cols[pos[0]] = DeviceColumn(key_out, key_col.pandas_dtype, length=n)
        ri = 0
        for i, c in enumerate(frame._columns):
            if i == pos[0]:
                continue
            new_cols[i] = DeviceColumn(rest[ri], c.pandas_dtype, length=n)
            ri += 1
        if kwargs.get("ignore_index", False):
            new_index = LazyIndex(pandas.RangeIndex(n), n)
        else:
            lazy = frame._index
            new_index = LazyIndex(
                lambda: lazy.get().take(np.asarray(perm_out)[:n]), n
            )
        return type(self)(
            TpuDataframe(new_cols, frame.columns, new_index, nrows=n)
        )

    def sort_rows_by_column_values(self, columns: Any, ascending: Any = True, **kwargs: Any) -> "TpuQueryCompiler":
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_sort(self, columns, ascending, kwargs)
            if planned is not None:
                return planned
        from modin_tpu.ops import sort as sort_ops

        if graftstream.STREAM_ON and _decide_windowed(
            "sort", (self._modin_frame,)
        ):
            # graftstream: external per-window sort + k-way run merge,
            # bit-identical to the resident paths below
            streamed = graftstream.external_sort_qc(
                self, columns, ascending, kwargs
            )
            if streamed is not None:
                return streamed

        range_result = self._try_range_partition_sort(columns, ascending, kwargs)
        if range_result is not None:
            return range_result

        if (
            kwargs.get("na_position", "last") == "last"
            and kwargs.get("key") is None
        ):
            frame = self._modin_frame
            col_list = [columns] if not isinstance(columns, list) else list(columns)
            asc = ascending if isinstance(ascending, list) else [ascending] * len(col_list)
            positions = []
            for label in col_list:
                pos = frame.column_position(label)
                if len(pos) != 1 or pos[0] < 0:
                    positions = None
                    break
                positions.append(pos[0])
            keys = None
            if positions is not None and len(frame) > 0:
                # sort keys: numeric device columns directly, host object/str
                # columns through their dictionary codes (sorted categories
                # make codes order-isomorphic — ops/dictionary.py); NaN codes
                # ride the kernels' existing na_position handling
                keys = []
                for p in positions:
                    kc = frame._columns[p]
                    if kc.is_device and kc.pandas_dtype.kind in "biuf":
                        keys.append(kc)
                    elif not kc.is_device:
                        from modin_tpu.ops.dictionary import encode_host_column

                        enc = encode_host_column(kc)
                        if enc is None:
                            keys = None
                            break
                        keys.append(enc[0])
                    else:
                        keys = None
                        break
            if keys is not None and all(
                c.is_device or hasattr(c.data, "take") for c in frame._columns
            ):
                from modin_tpu.ops.structural import gather_columns_device

                n = len(frame)
                frame.materialize_device()
                perm = sort_ops.lexsort_permutation(
                    [k.data for k in keys], n, [bool(a) for a in asc]
                )
                dev_positions = [
                    i for i, c in enumerate(frame._columns) if c.is_device
                ]
                datas = gather_columns_device(
                    [frame._columns[i].data for i in dev_positions], perm
                )
                dev_iter = iter(datas)
                perm_h = None
                new_cols: list = []
                for c in frame._columns:
                    if c.is_device:
                        new_cols.append(
                            DeviceColumn(next(dev_iter), c.pandas_dtype, length=n)
                        )
                    else:
                        # host (object/str) payloads reorder by the fetched
                        # permutation — one n-int fetch shared by all of them
                        if perm_h is None:
                            perm_h = np.asarray(perm)[:n]
                        new_cols.append(HostColumn(c.data.take(perm_h)))
                if kwargs.get("ignore_index", False):
                    new_index = LazyIndex(pandas.RangeIndex(n), n)
                else:
                    lazy = frame._index
                    new_index = LazyIndex(
                        lambda: lazy.get().take(np.asarray(perm)[:n]), n
                    )
                return type(self)(
                    TpuDataframe(new_cols, frame.columns, new_index, nrows=n)
                )
        return super().sort_rows_by_column_values(columns, ascending=ascending, **kwargs)


# ---------------------------------------------------------------------- #
# Generated overrides: binary ops and reductions try the device path and
# fall back to the inherited defaults.
# ---------------------------------------------------------------------- #

def _make_binary_override(op: str):
    base_method = getattr(BaseQueryCompiler, op)

    def method(self: TpuQueryCompiler, other: Any, **kwargs: Any):
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.defer_binary(self, op, other, kwargs)
            if planned is not None:
                return planned
        result = self._try_device_binary(op, other, kwargs)
        if result is not None:
            return result
        return base_method(self, other, **kwargs)

    method.__name__ = op
    return method


for _op in [
    "add", "radd", "sub", "rsub", "mul", "rmul", "truediv", "rtruediv",
    "floordiv", "rfloordiv", "mod", "rmod", "pow", "rpow",
    "eq", "ne", "lt", "le", "gt", "ge",
    "__and__", "__or__", "__xor__", "__rand__", "__ror__", "__rxor__",
]:
    setattr(TpuQueryCompiler, _op, _make_binary_override(_op))


def _make_reduce_override(op: str):
    base_method = getattr(BaseQueryCompiler, op)

    def method(
        self: TpuQueryCompiler,
        axis: Any = 0,
        skipna: bool = True,
        numeric_only: bool = False,
        **kwargs: Any,
    ):
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.run_reduce(
                self,
                op,
                dict(axis=axis, skipna=skipna, numeric_only=numeric_only, **kwargs),
            )
            if planned is not None:
                return planned
        result = self._try_device_reduce(op, axis, skipna, numeric_only, kwargs)
        if result is not None:
            return result
        return base_method(
            self, axis=axis, skipna=skipna, numeric_only=numeric_only, **kwargs
        )

    method.__name__ = op
    return method


for _op in ["sum", "prod", "mean", "median", "min", "max", "var", "std", "sem", "skew", "kurt"]:
    setattr(TpuQueryCompiler, _op, _make_reduce_override(_op))


def _make_nonskipna_reduce_override(op: str):
    base_method = getattr(BaseQueryCompiler, op)

    def method(self: TpuQueryCompiler, axis: Any = 0, **kwargs: Any):
        skipna = kwargs.pop("skipna", True)
        numeric_only = kwargs.pop("numeric_only", False)
        if self._plan is not None or graftplan.FORCE_ON:
            planned = graftplan.run_reduce(
                self,
                op,
                dict(axis=axis, skipna=skipna, numeric_only=numeric_only, **kwargs),
            )
            if planned is not None:
                return planned
        result = self._try_device_reduce(op, axis, skipna, numeric_only, kwargs)
        if result is not None:
            return result
        if op == "count":
            return base_method(self, axis=axis, numeric_only=numeric_only, **kwargs)
        return base_method(self, axis=axis, skipna=skipna, **kwargs)

    method.__name__ = op
    return method


for _op in ["count", "any", "all"]:
    setattr(TpuQueryCompiler, _op, _make_nonskipna_reduce_override(_op))

RESAMPLE_DEVICE_OPS = ("sum", "mean", "count", "min", "max", "var", "std", "size")


def _make_resample_override(op: str):
    def method(self, resample_kwargs: dict, *args: Any, **kwargs: Any):
        result = (
            self._try_device_resample(op, resample_kwargs, dict(kwargs))
            if not args
            else None
        )
        if result is not None:
            return result
        return getattr(super(TpuQueryCompiler, self), f"resample_{op}")(
            resample_kwargs, *args, **kwargs
        )

    method.__name__ = f"resample_{op}"
    return method


def _make_rolling_override(op: str):
    def method(self, rolling_kwargs: dict, *args: Any, **kwargs: Any):
        result = (
            self._try_device_rolling(op, rolling_kwargs, dict(kwargs))
            if not args
            else None
        )
        if result is not None:
            return result
        return getattr(super(TpuQueryCompiler, self), f"rolling_{op}")(
            rolling_kwargs, *args, **kwargs
        )

    method.__name__ = f"rolling_{op}"
    return method


def _make_expanding_override(op: str):
    def method(self, expanding_args: list, *args: Any, **kwargs: Any):
        result = (
            self._try_device_expanding(op, list(expanding_args), dict(kwargs))
            if not args
            else None
        )
        if result is not None:
            return result
        return getattr(super(TpuQueryCompiler, self), f"expanding_{op}")(
            expanding_args, *args, **kwargs
        )

    method.__name__ = f"expanding_{op}"
    return method


def _make_ewm_override(op: str):
    def method(self, ewm_kwargs: dict, *args: Any, **kwargs: Any):
        result = (
            self._try_device_ewm(op, ewm_kwargs, dict(kwargs))
            if not args
            else None
        )
        if result is not None:
            return result
        return getattr(super(TpuQueryCompiler, self), f"ewm_{op}")(
            ewm_kwargs, *args, **kwargs
        )

    method.__name__ = f"ewm_{op}"
    return method


from modin_tpu.ops.window import (  # noqa: E402
    EWM_DEVICE_OPS as _EWM_OPS,
    EXPANDING_DEVICE_OPS as _EXP_OPS,
    ROLLING_DEVICE_OPS as _ROLL_OPS,
)

for _op in _ROLL_OPS:
    setattr(TpuQueryCompiler, f"rolling_{_op}", _make_rolling_override(_op))
for _op in _EXP_OPS:
    setattr(TpuQueryCompiler, f"expanding_{_op}", _make_expanding_override(_op))
for _op in _EWM_OPS:
    setattr(TpuQueryCompiler, f"ewm_{_op}", _make_ewm_override(_op))
for _op in RESAMPLE_DEVICE_OPS:
    setattr(TpuQueryCompiler, f"resample_{_op}", _make_resample_override(_op))


# string predicates/measures whose per-category results gather by dictionary
# code on device (_try_str_lut); string-OUTPUT ops (lower/strip/replace/...)
# stay host-side by design
_STR_LUT_METHODS = [
    "len", "count", "contains", "startswith", "endswith", "match",
    "fullmatch", "find", "rfind", "isalnum", "isalpha", "isdigit",
    "isspace", "islower", "isupper", "istitle", "isnumeric", "isdecimal",
]


def _make_str_lut_override(name: str):
    base = getattr(BaseQueryCompiler, f"str_{name}")

    def method(self: TpuQueryCompiler, *args: Any, **kwargs: Any):
        result = self._try_str_lut(name, args, kwargs)
        if result is not None:
            return result
        return base(self, *args, **kwargs)

    method.__name__ = f"str_{name}"
    return method


for _op in _STR_LUT_METHODS:
    if getattr(BaseQueryCompiler, f"str_{_op}", None) is not None:
        setattr(TpuQueryCompiler, f"str_{_op}", _make_str_lut_override(_op))


def _make_dt_component_override(name: str):
    base = getattr(BaseQueryCompiler, f"dt_{name}")

    def method(self: TpuQueryCompiler, *args: Any, **kwargs: Any):
        result = self._try_dt_component(name, args, kwargs)
        if result is not None:
            return result
        return base(self, *args, **kwargs)

    method.__name__ = f"dt_{name}"
    return method


from modin_tpu.ops.datetime_parts import (  # noqa: E402
    COMPONENT_NAMES as _DT_COMPONENTS,
    TIMEDELTA_COMPONENT_NAMES as _TD_COMPONENTS,
)

for _op in _DT_COMPONENTS:
    if getattr(BaseQueryCompiler, f"dt_{_op}", None) is not None:
        setattr(
            TpuQueryCompiler, f"dt_{_op}", _make_dt_component_override(_op)
        )


def _make_td_component_override(name: str):
    base = getattr(BaseQueryCompiler, f"dt_{name}")

    def method(self: TpuQueryCompiler, *args: Any, **kwargs: Any):
        result = self._try_td_component(name, args, kwargs)
        if result is not None:
            return result
        return base(self, *args, **kwargs)

    method.__name__ = f"dt_{name}"
    return method


for _op in _TD_COMPONENTS:
    if getattr(BaseQueryCompiler, f"dt_{_op}", None) is not None:
        setattr(
            TpuQueryCompiler, f"dt_{_op}", _make_td_component_override(_op)
        )

# the generated overrides above were installed after __init_subclass__ ran,
# so they need the backend-caster wrap applied explicitly
from modin_tpu.core.storage_formats.base.query_compiler_caster import (  # noqa: E402
    wrap_query_compiler_methods as _wrap_qc_methods,
)

_wrap_qc_methods(TpuQueryCompiler)
