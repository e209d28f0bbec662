"""``TpuDataframe`` — the sharded columnar core frame.

TPU-native re-design of the reference's core frame
(modin/core/dataframe/pandas/dataframe/dataframe.py:82).  Instead of a 2-D
grid of pandas-block partitions on worker processes, a frame is:

- host metadata: column labels (pandas.Index), a lazy row index (LazyIndex),
  per-column logical dtypes;
- per column, either a **DeviceColumn** (1-D jax.Array sharded over the mesh
  "rows" axis — row-partitioning is the sharding spec, SURVEY.md §7) or a
  **HostColumn** (numpy/extension array for object/string dtypes — the
  device/host split that replaces the reference's default-to-pandas partition
  fallback).  A ``category`` column is ingested as a HostColumn and becomes
  a DeviceColumn (``is_category``) the first time a device path reads it (a
  groupby key): its integer codes on the device at the width pandas holds
  them (-1 = missing), its ``CategoricalDtype`` on the host.  The frame keeps
  the resident column from then on, and an answer's category key column is
  one from the start.

Device columns are **padded** to a multiple of the mesh row-shard count with
the logical length tracked per column: XLA requires even shards for
explicitly sharded arrays, and uneven results silently fall back to
replication.  All device kernels (modin_tpu/ops/) are pad-aware.

Datetimes/timedeltas live on device as int64 with a logical-dtype tag; NaT is
the int64 min sentinel, exactly pandas' own representation, so the round-trip
is a zero-cost view.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Union

import numpy as np
import pandas

from pandas.api.types import is_object_dtype as _is_object_dtype

from modin_tpu.core.dataframe.base.dataframe import BaseDataframe
from modin_tpu.core.dataframe.tpu.metadata import LazyIndex, ensure_index
from modin_tpu.logging import ClassLogger, disable_logging
from modin_tpu.observability import meters as _meters

_DEVICE_NUMPY_KINDS = "biuf"  # bool, int, uint, float


def _is_device_dtype(dtype: Any) -> bool:
    """Whether a pandas dtype can live on device."""
    if not isinstance(dtype, np.dtype):
        return False
    if dtype.kind in _DEVICE_NUMPY_KINDS and dtype.itemsize <= 8:
        return True
    # naive datetime64/timedelta64 (any unit) as int64 + logical tag; the NaT
    # sentinel (int64 min) is unit-independent
    return dtype.kind in "mM" and dtype.itemsize == 8


def _device_layout_values(values: np.ndarray) -> np.ndarray:
    """The dtype-policy transform of host values for device residence
    (datetime int64 view, Downcast float32 policy, contiguity).  The ONE
    transform shared by full uploads (``_device_put_values``) and the
    graftmesh single-shard re-seat, so a recovered shard's slice is always
    byte-identical to what a full upload would have put there.
    """
    from modin_tpu.config import Float64Policy

    device_values = values.view("int64") if values.dtype.kind in "mM" else values
    if device_values.dtype == np.float64 and Float64Policy.get() == "Downcast":
        # f64 on TPU is double-float emulated (~2x the FLOPs, half the
        # VPU/MXU rate); the Downcast policy stores f32 on device while
        # the logical dtype and host_cache keep exact float64 — the user
        # opts into f32 compute precision for device kernels.
        device_values = device_values.astype(np.float32)
    if not device_values.flags.c_contiguous:
        device_values = np.ascontiguousarray(device_values)
    return device_values


def _device_put_values(values: np.ndarray, sharding: Any = None) -> Any:
    """Host values -> padded device buffer under the dtype policy.

    The transform ``from_numpy`` applies (datetime int64 view, Downcast
    float32 policy, contiguity, shard padding), shared with the graftguard
    spill-restore and lineage re-seat paths so a recovered buffer is
    byte-identical to the original upload.
    """
    from modin_tpu.ops.structural import pad_host
    from modin_tpu.parallel.engine import JaxWrapper

    return JaxWrapper.put(pad_host(_device_layout_values(values)), sharding)


class DeviceColumn:
    """One column as a padded 1-D jax.Array sharded over the mesh rows axis.

    ``length`` is the logical row count (data.shape[0] is padded up to a
    multiple of the shard count; pad rows are never read).

    ``pandas_dtype`` is the logical dtype: an ``np.dtype``, or — a **category
    column** (``is_category``) — the frame's own ``CategoricalDtype``, shared
    and never copied.  A category column's buffer (``data``, ``host_cache``)
    holds its integer codes as pandas holds them (int8 up to 127 categories,
    int16, int32; -1 = missing); that is its one representation.  Row-moving
    operations and a groupby key take the codes like any integer column;
    everything that reads *values* has to look at ``is_category`` (or at
    ``pandas_dtype.kind``, ``"O"`` here) and decline, and the pandas default
    then answers through ``to_pandas_array`` (one ``Categorical.from_codes``).

    ``host_cache`` keeps the original (unpadded) host numpy array for columns
    that came from the host unchanged: it makes device round-trips bit-exact
    even where the accelerator emulates the dtype (TPU f64 is double-float:
    ~2^-49 relative precision with a float32 exponent range) and lets the
    default-to-pandas path skip the device->host transfer entirely.  Any
    computed column drops the cache.

    graftguard state (core/execution/recovery.py, core/memory.py):
    ``lineage`` is the creation-time provenance record; ``_device_epoch``
    stamps which device incarnation the buffer belongs to; ``_dev_key``
    is the device-memory ledger handle.  A **spilled** column has
    ``_data is None`` and an exact ``host_cache`` — the buffer restores
    transparently on the next ``raw``/``data`` access.
    """

    __slots__ = (
        "_data", "pandas_dtype", "length", "host_cache", "_ledger_key",
        "lineage", "_device_epoch", "_dev_key", "_sorted_rep", "donated",
        "_view_token", "_view_parent",
        "__weakref__",
    )
    is_device = True

    def __init__(
        self,
        data: Any,
        pandas_dtype: np.dtype,
        length: Optional[int] = None,
        host_cache: Optional[np.ndarray] = None,
    ):
        # data: concrete jax.Array OR a deferred LazyExpr (ops/lazy.py);
        # lazy columns materialize on .data access — fusion-aware consumers
        # read .raw instead to keep chains deferred.
        self._data = data
        self.pandas_dtype = (
            pandas_dtype
            if isinstance(pandas_dtype, pandas.CategoricalDtype)
            else np.dtype(pandas_dtype)
        )
        self.length = int(length) if length is not None else int(data.shape[0])
        self.host_cache = host_cache
        self._ledger_key = None
        self.lineage = None
        self._device_epoch = 0
        self._dev_key = None
        self._sorted_rep = None  # graftsort: cached (sorted, n_valid) rep
        self.donated = False  # graftfuse: buffer consumed by a donated dispatch
        # graftview identity: process-unique token (lazily allocated) and
        # the (parent_token, parent_length) append link
        self._view_token = None
        self._view_parent = None
        if host_cache is not None:
            # host caches count against the Memory spill budget (core/memory.py)
            from modin_tpu.core.memory import ledger

            ledger.register(self)
        from modin_tpu.ops.lazy import LazyExpr

        if data is not None and not isinstance(data, LazyExpr):
            # a LazyExpr (even a memoized one) registers on materialization;
            # only a concrete device buffer belongs in the ledgers
            self._register_device()
            from modin_tpu.core.execution import recovery

            recovery.attach_lineage(self)

    @property
    def data(self) -> Any:
        from modin_tpu.ops.lazy import LazyExpr, materialize

        if self._data is None:
            self._restore()
        if isinstance(self._data, LazyExpr):
            self._data = materialize(self._data)
            self._on_materialized()
        return self._data

    @property
    def raw(self) -> Any:
        """The underlying array or deferred expression, unmaterialized
        (a spilled column transparently restores its device buffer)."""
        if self._data is None:
            self._restore()
        return self._data

    @property
    def is_lazy(self) -> bool:
        from modin_tpu.ops.lazy import is_lazy

        return is_lazy(self._data)

    @property
    def is_category(self) -> bool:
        """The buffer holds a ``CategoricalDtype``'s codes, not values."""
        return isinstance(self.pandas_dtype, pandas.CategoricalDtype)

    @property
    def is_spilled(self) -> bool:
        """Device buffer dropped; host_cache is the (exact) only copy."""
        return self._data is None

    # -- graftguard: ledger registration, spill/restore, re-seat -------- #

    def _register_device(self) -> None:
        """Track the concrete buffer in the device-memory ledger and stamp
        the current device epoch (recovery provenance indexing rides on
        the same registration)."""
        from modin_tpu.core.execution import recovery
        from modin_tpu.core.memory import device_ledger

        device_ledger.register(self)
        self._device_epoch = recovery.current_epoch()
        self.donated = False  # a fresh buffer: the donation is history
        recovery.note_column_data(self)

    def _on_materialized(self) -> None:
        """A deferred expression just became a concrete device buffer."""
        from modin_tpu.core.execution import recovery

        self._invalidate_sorted()
        self._register_device()
        recovery.attach_lineage(self)

    def _invalidate_sorted(self) -> None:
        """Drop every derived cache answering for this column's buffer —
        it is about to change (spill / re-seat / materialize / donation):
        the graftsort sorted rep and every graftview artifact registered
        under the column's token."""
        if self._sorted_rep is not None:
            from modin_tpu.ops.sorted_cache import invalidate

            invalidate(self)
        if self._view_token is not None:
            from modin_tpu.views import registry as views_registry

            views_registry.invalidate_column(self, reason="buffer")

    def spill(self) -> int:
        """Drop the device buffer, keeping an exact host copy; returns the
        device bytes freed (0 = not spillable right now)."""
        if self._data is None or self.is_lazy:
            return 0
        # a sorted rep derived from the buffer being dropped must not
        # outlive it (and holding it would defeat the spill anyway)
        self._invalidate_sorted()
        cache = self.host_cache
        if cache is None:
            # the fetch round-trips the logical dtype exactly (and under
            # Downcast the f32 device value widens losslessly), so the
            # host copy reproduces the device buffer bit-for-bit
            cache = self.buffer_to_numpy()
        from modin_tpu.core.memory import device_ledger

        freed = device_ledger.deregister(self)
        # drop the buffer BEFORE registering the cache: is_spilled must be
        # True when the host ledger's enforce() runs, or a tight Memory
        # budget could evict the sole copy the moment it is registered
        self._data = None
        if self.host_cache is None:
            self.adopt_host_cache(cache)
        return freed

    # -- graftfuse: buffer donation ------------------------------------- #

    def donation_eligible(self) -> bool:
        """The LOCAL half of the donation proof: a concrete resident
        buffer with an exact host copy to restore from (the lineage-replay
        contract: after donation the column is *spilled*, and the next
        access transparently re-uploads).  The sole-consumer half comes
        from the device ledger — ``donation_safe`` for one column,
        ``buffer_consumer_counts`` for a whole dispatch's batch."""
        return (
            self._data is not None
            and not self.is_lazy
            and self.host_cache is not None
        )

    def donation_safe(self) -> bool:
        """Whether this column's buffer may ride in a donated jit position:
        :meth:`donation_eligible` plus the device ledger's proof that no
        OTHER live column holds the same buffer — donating a shared buffer
        would delete it under its other owner mid-use."""
        if not self.donation_eligible():
            return False
        from modin_tpu.core.memory import device_ledger

        return device_ledger.buffer_consumers(self._data) == 1

    def mark_donated(self) -> int:
        """Record that a donated dispatch consumed this column's buffer.

        The column becomes *spilled* (``_data is None`` with the exact host
        copy authoritative): every later read restores via lineage — a
        fresh upload — instead of touching the consumed buffer, which is
        exactly the use-after-donate guard.  Returns the device bytes
        released from the ledger (the HBM the donation reclaimed).
        """
        if self._data is None or self.is_lazy:
            return 0
        # a sorted rep derived from the consumed buffer must not outlive it
        self._invalidate_sorted()
        from modin_tpu.core.memory import device_ledger

        freed = device_ledger.deregister(self)
        self._data = None
        self.donated = True
        return freed

    def _restore(self) -> None:
        """Re-seat a spilled column's device buffer from its host copy."""
        if self.host_cache is None:
            raise RuntimeError(
                "spilled DeviceColumn has no host copy to restore from"
            )
        was_donated = self.donated  # reseat stamps the fresh buffer clean
        self.reseat_from_host()
        from modin_tpu.logging.metrics import emit_metric

        emit_metric("memory.device.restore", 1)
        if was_donated:
            # the use-after-donate guard doing its job: a buffer a fused
            # dispatch consumed was rebuilt via lineage on first re-access
            emit_metric("fuse.donated_restore", 1)

    def reseat_from_host(self) -> None:
        """Upload the exact host copy as a fresh device buffer (lineage
        kind 'host'; also the spill-restore path)."""
        values = self.host_cache  # single read: eviction may race us
        if values is None:
            raise RuntimeError("no host copy to re-seat from")
        self._invalidate_sorted()
        self._data = _device_put_values(np.asarray(values))
        self._register_device()

    def reseat_from_host_shard(self, shard_index: int) -> bool:
        """Re-seat ONLY one lost shard's slice from the exact host copy,
        keeping every live shard's device buffer (graftmesh single-shard
        recovery).  Returns False when not applicable — no host copy, a
        lazy/spilled column, a single-shard mesh, an uneven layout, or any
        failure reading the surviving shards (a real whole-device loss) —
        and the caller takes the full re-seat path instead.
        """
        values = self.host_cache  # single read: eviction may race us
        data = self._data
        if values is None or data is None or self.is_lazy:
            return False
        try:
            import jax

            from modin_tpu.parallel.mesh import num_row_shards

            S = num_row_shards()
            P = int(data.shape[0])
            if S < 2 or not (0 <= int(shard_index) < S) or P % S:
                return False
            L = P // S
            start = int(shard_index) * L
            # the ONE shared host->device transform (_device_layout_values,
            # exactly what a full upload applies), restricted to the lost
            # shard's row range (pad rows zero)
            dev_vals = _device_layout_values(np.asarray(values))
            sl = np.ascontiguousarray(dev_vals[start : start + L])
            if len(sl) < L:
                sl = np.concatenate(
                    [sl, np.zeros(L - len(sl), dtype=sl.dtype)]
                )
            by_start = {}
            for sh in data.addressable_shards:
                idx = sh.index[0]
                by_start[int(idx.start or 0)] = sh
            if len(by_start) != S or start not in by_start:
                return False
            arrays = []
            for st in sorted(by_start):
                sh = by_start[st]
                if st == start:
                    arrays.append(jax.device_put(sl, sh.device))
                    if _meters.ACCOUNTING_ON:
                        _meters.note_h2d(int(sl.nbytes))
                else:
                    # touching a dead device's buffer raises here, which is
                    # exactly the signal to fall back to the full re-seat
                    arrays.append(sh.data)
            fresh = jax.make_array_from_single_device_arrays(
                data.shape, data.sharding, arrays
            )
        except Exception:  # graftlint: disable=EXC-HYGIENE -- the single-shard leg is an optimization; ANY failure (dead neighbor shards, exotic sharding) falls back to the whole-column re-seat
            return False
        self._invalidate_sorted()
        self._data = fresh
        self._register_device()
        return True

    def shard_valid_counts(self) -> np.ndarray:
        """Per-shard valid-row counts under the padded prefix layout:
        leading shards are full, one shard is ragged, trailing pad shards
        are empty.  The per-shard valid-row accounting of the SPMD layout
        (docs/architecture.md "SPMD execution & the mesh substrate"): the
        padded-bytes ledger splits evenly, this answers how much of each
        shard's slice is live data.

        Uses the concrete buffer's physical length when it divides the
        current shard count; a buffer laid out under a different mesh (or
        a lazy/spilled column) answers for the canonical current-mesh
        padding instead.
        """
        from modin_tpu.ops.structural import pad_len
        from modin_tpu.parallel.mesh import num_row_shards

        S = max(num_row_shards(), 1)
        data = self._data
        P = (
            int(data.shape[0])
            if data is not None and hasattr(data, "shape")
            else pad_len(self.length)
        )
        if P % S:
            P = pad_len(self.length)
        L = P // S
        return np.clip(
            self.length - np.arange(S, dtype=np.int64) * L, 0, L
        )

    def adopt_reseated(self, data: Any) -> None:
        """Adopt a lineage-replayed device buffer (op-replay recovery)."""
        self._invalidate_sorted()
        self._data = data
        self._register_device()

    def adopt_host_cache(self, values: np.ndarray) -> None:
        """Take ``values`` as the exact host copy (registered against the
        host-memory budget like every other cache)."""
        self.host_cache = values
        from modin_tpu.core.memory import ledger

        ledger.register(self)

    def host_checkpoint(self) -> None:
        """Pin the exact host copy (lineage depth cut-point): one fetch now
        makes this column depth-0 recoverable forever after."""
        if self.host_cache is None:
            self.adopt_host_cache(self.buffer_to_numpy())

    @classmethod
    def from_numpy(cls, values: np.ndarray, sharding: Any = None) -> "DeviceColumn":
        return cls(
            _device_put_values(values, sharding),
            values.dtype,
            length=len(values),
            host_cache=values,
        )

    @classmethod
    def from_categorical(cls, cat: pandas.Categorical) -> "DeviceColumn":
        """A category column: pandas' own codes array uploaded as it is (no
        cast, no copy on the host), the dtype object shared."""
        codes = np.asarray(cat.codes)
        return cls(
            _device_put_values(codes), cat.dtype, length=len(codes), host_cache=codes
        )

    def to_numpy(self) -> np.ndarray:
        """The column's values on the host (a category column's decoded, as
        ``HostColumn.to_numpy`` gives them)."""
        if self.is_category:
            return np.asarray(self.to_pandas_array())
        return self.buffer_to_numpy()

    def to_pandas_array(self) -> Any:
        """What ``to_pandas`` puts in the frame: the values, or for a category
        column the ``Categorical`` of the fetched codes (the one decode)."""
        values = self.buffer_to_numpy()
        if self.is_category:
            return pandas.Categorical.from_codes(values, dtype=self.pandas_dtype)
        return values

    def buffer_to_numpy(self) -> np.ndarray:
        """The buffer's logical rows on the host, exactly: values, or a
        category column's codes."""
        from modin_tpu.parallel.engine import JaxWrapper

        cache = self.host_cache  # single read: eviction may race us
        if cache is not None:
            from modin_tpu.core.memory import ledger

            ledger.touch(self)
            return cache
        try:
            values = np.asarray(JaxWrapper.materialize(self.data))[: self.length]
        except Exception as err:  # graftlint: disable=EXC-HYGIENE -- recovery gate: recover_for_read re-seats only on a classified DeviceLost and this re-raises otherwise
            from modin_tpu.core.execution.recovery import recover_for_read

            if not recover_for_read(self, err):
                raise
            # the column was re-seated from lineage: one fetch retry
            values = np.asarray(JaxWrapper.materialize(self.data))[: self.length]
        if self.is_category:
            return values
        if self.pandas_dtype.kind in "mM":
            values = values.view(self.pandas_dtype)
        elif values.dtype != self.pandas_dtype:
            # Float64Policy=Downcast stores f32 on device for a logical f64
            values = values.astype(self.pandas_dtype)
        return values

    def with_data(
        self,
        data: Any,
        pandas_dtype: Optional[np.dtype] = None,
        length: Optional[int] = None,
    ) -> "DeviceColumn":
        return DeviceColumn(
            data,
            pandas_dtype if pandas_dtype is not None else self.pandas_dtype,
            length if length is not None else self.length,
        )

    def __len__(self) -> int:
        return self.length


class HostColumn:
    """One column kept on host (object/string/extension dtypes, and a
    ``category`` column until a device path first reads its codes).

    ``_dict_cache`` lazily holds the column's dictionary encoding — (codes
    DeviceColumn, SORTED categories) — or False once found unencodable (see
    ops/dictionary.py).  ``_cat_cache`` holds a categorical column's resident
    form once made (``ops/dictionary.py`` ``resident_category_column``: a
    category :class:`DeviceColumn` of pandas' own codes, in CATEGORY order —
    never served to the sorted dictionary's consumers), or False.  Columns
    are replaced, never mutated in place, so the caches cannot go stale.
    """

    # __weakref__: graftview host-identity guards (views/groupby_cache.py)
    # pin cached results to the exact live column objects via weakrefs
    __slots__ = ("data", "_dict_cache", "_cat_cache", "__weakref__")
    is_device = False
    is_category = False  # a DeviceColumn's flag: codes in the device buffer

    def __init__(self, data: Any):
        # data: 1-D numpy array or pandas ExtensionArray (unpadded)
        self.data = data
        self._dict_cache = None
        self._cat_cache = None

    @property
    def pandas_dtype(self):
        return self.data.dtype

    @property
    def length(self) -> int:
        return len(self.data)

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.data)

    def to_pandas_array(self) -> Any:
        return self.data

    def __len__(self) -> int:
        return len(self.data)


Column = Union[DeviceColumn, HostColumn]


def _same_code_tables(cols: List[DeviceColumn]) -> bool:
    """Whether device columns' buffers mean the same thing row for row: no
    category column among them, or all of them with the same categories in the
    same order (equal unordered ``CategoricalDtype``s may number them
    differently)."""
    if not any(c.is_category for c in cols):
        return True
    first = cols[0].pandas_dtype
    return all(
        c.is_category
        and c.pandas_dtype.ordered == first.ordered
        and (
            c.pandas_dtype.categories is first.categories
            or c.pandas_dtype.categories.equals(first.categories)
        )
        for c in cols
    )


class TpuDataframe(BaseDataframe, ClassLogger, modin_layer="CORE-FRAME"):
    """Columnar frame: host metadata + device/host column store.

    Implements the abstract structural algebra
    (core/dataframe/base/dataframe.py BaseDataframe; reference
    modin/core/dataframe/base/dataframe/dataframe.py:26)."""

    @disable_logging  # field assignments: one to three a request
    def __init__(
        self,
        columns: List[Column],
        col_labels: pandas.Index,
        index: Union[pandas.Index, LazyIndex, Callable],
        nrows: Optional[int] = None,
    ):
        self._columns = columns
        self._col_labels = ensure_index(col_labels)
        if not isinstance(index, LazyIndex):
            index = LazyIndex(index, nrows)
        self._index = index

    # ------------------------------------------------------------------ #
    # Construction / materialization
    # ------------------------------------------------------------------ #

    @classmethod
    def from_pandas(cls, df: pandas.DataFrame) -> "TpuDataframe":
        from modin_tpu.core.execution.resilience import DeviceFailure

        columns: List[Column] = []
        for i in range(df.shape[1]):
            series = df.iloc[:, i]
            dtype = series.dtype
            if isinstance(dtype, np.dtype) and _is_device_dtype(dtype):
                values = series.to_numpy()
                try:
                    columns.append(DeviceColumn.from_numpy(values))
                except DeviceFailure:
                    # upload failed (device OOM / lost): keep the column on
                    # host — every device path declines host columns and the
                    # pandas defaults answer, so ingest degrades instead of
                    # crashing (the engine seam already emitted the metric).
                    # The raw ndarray, NOT series.array: a
                    # NumpyExtensionArray's NumpyEADtype compares unequal to
                    # the np.dtype every dispatch check expects.
                    columns.append(HostColumn(values))
            else:
                arr = series.array.copy()
                if isinstance(arr, pandas.arrays.NumpyExtensionArray):
                    # store the raw ndarray: NumpyEADtype('object') fails ==
                    # against np.dtype(object) and would leak to users as a
                    # different-looking dtype
                    arr = np.asarray(arr)
                columns.append(HostColumn(arr))
        return cls(columns, df.columns, df.index, nrows=len(df))

    def to_pandas(self) -> pandas.DataFrame:
        self.materialize_device()
        idx = self.index
        data = {}
        for i, col in enumerate(self._columns):
            if col.is_device:
                data[i] = col.to_pandas_array()
            else:
                arr = col.to_pandas_array()
                if _is_object_dtype(getattr(arr, "dtype", None)):
                    # pandas 3 infers str for plain object string arrays;
                    # an explicit-dtype Series is the only construction
                    # that round-trips object EXACTLY
                    arr = pandas.Series(arr, index=idx, dtype=object)
                data[i] = arr
        df = pandas.DataFrame(data, index=idx, copy=False)
        df.columns = self._col_labels
        return df

    def to_numpy(self, **kwargs: Any) -> np.ndarray:
        return self.to_pandas().to_numpy(**kwargs)

    # ------------------------------------------------------------------ #
    # Metadata
    # ------------------------------------------------------------------ #

    @property
    def index(self) -> pandas.Index:
        return self._index.get()

    @index.setter
    def index(self, value: Any) -> None:
        value = ensure_index(value)
        assert len(value) == len(self), "Length mismatch"
        self._index = LazyIndex(value)

    @property
    def columns(self) -> pandas.Index:
        return self._col_labels

    @columns.setter
    def columns(self, value: Any) -> None:
        value = ensure_index(value)
        assert len(value) == len(self._columns), "Length mismatch"
        self._col_labels = value

    @property
    def dtypes(self) -> pandas.Series:
        return pandas.Series(
            [col.pandas_dtype for col in self._columns], index=self._col_labels
        )

    @disable_logging  # trivial accessors: no span, no wrapper frames
    def __len__(self) -> int:
        if self._columns:
            return self._columns[0].length
        return len(self.index)

    @property
    def num_cols(self) -> int:
        return len(self._columns)

    @property
    def all_device(self) -> bool:
        """Every column holds values on the device (no host column, and no
        category column, whose buffer holds codes)."""
        return all(col.is_device and not col.is_category for col in self._columns)

    def copy(self) -> "TpuDataframe":
        return TpuDataframe(
            list(self._columns), self._col_labels, self._index.copy()
        )

    def materialize_device(self) -> None:
        """Batch-materialize all deferred device columns in ONE fused jit.

        Multi-column consumers call this before touching ``.data`` so a frame
        of N lazy columns costs one dispatch, not N (the one-jit-per-operator
        invariant, extended to the fusion layer).
        """
        from modin_tpu.ops.lazy import materialize_exprs

        lazy_cols = [c for c in self._columns if c.is_device and c.is_lazy]
        if not lazy_cols:
            return
        results = materialize_exprs([c.raw for c in lazy_cols])
        for col, value in zip(lazy_cols, results):
            col._data = value
            col._on_materialized()

    def finalize(self) -> None:
        """Block until device work for this frame completes (one sync).

        Columns with a ``host_cache`` are skipped: their values are already
        known on the host (the device buffer is a pending *upload*, not
        pending compute), so there is nothing observable to wait for — any
        downstream device op consuming the buffer orders after the transfer
        on-device.  Blocking on them costs a host sync per call for no
        information.
        """
        from modin_tpu.parallel.engine import JaxWrapper

        self.materialize_device()
        device_data = [
            col.data
            for col in self._columns
            if col.is_device and col.host_cache is None
        ]
        if device_data:
            JaxWrapper.wait(device_data)

    def free(self) -> None:
        self._columns = []

    # ------------------------------------------------------------------ #
    # Structural algebra (host-metadata ops are free; device ops dispatch
    # one jit per frame, fused across columns)
    # ------------------------------------------------------------------ #

    def select_columns_by_position(self, positions: Sequence[int]) -> "TpuDataframe":
        return TpuDataframe(
            [self._columns[i] for i in positions],
            self._col_labels[list(positions)],
            self._index,
        )

    def rename_columns(self, new_labels: pandas.Index) -> "TpuDataframe":
        return TpuDataframe(list(self._columns), new_labels, self._index)

    def with_columns(
        self,
        columns: List[Column],
        col_labels: Optional[pandas.Index] = None,
        index: Optional[Union[pandas.Index, LazyIndex]] = None,
        nrows: Optional[int] = None,
    ) -> "TpuDataframe":
        return TpuDataframe(
            columns,
            col_labels if col_labels is not None else self._col_labels,
            index if index is not None else self._index,
            nrows=nrows,
        )

    def take_rows_positional(self, positions: Any) -> "TpuDataframe":
        """Gather rows by position (pad-aware device gather, one jit)."""
        n = len(self)
        if isinstance(positions, slice):
            positions = np.arange(*positions.indices(n), dtype=np.int64)
        else:
            positions = np.asarray(positions, dtype=np.int64)
            positions = np.where(positions < 0, positions + n, positions)
        return self._take_host_positions(positions)

    def _take_host_positions(self, pos_arr: np.ndarray) -> "TpuDataframe":
        from modin_tpu.ops.structural import gather_columns

        self.materialize_device()
        device_idx = [i for i, c in enumerate(self._columns) if c.is_device]
        new_columns: List[Column] = list(self._columns)
        if device_idx:
            datas, n_out = gather_columns(
                [self._columns[i].data for i in device_idx], pos_arr
            )
            for i, d in zip(device_idx, datas):
                col = self._columns[i]
                src = col.host_cache  # single read: eviction may race us
                cache = src.take(pos_arr) if src is not None else None
                new_columns[i] = DeviceColumn(
                    d, col.pandas_dtype, length=len(pos_arr), host_cache=cache
                )
        for i, col in enumerate(self._columns):
            if not col.is_device:
                new_columns[i] = HostColumn(col.data.take(pos_arr))
        new_index = self._index.map_after(lambda idx: idx.take(pos_arr), len(pos_arr))
        return self.with_columns(new_columns, index=new_index, nrows=len(pos_arr))

    def filter_rows_mask(self, mask: Any) -> "TpuDataframe":
        """Boolean-mask rows.  The row count is data-dependent, so this is an
        eager (synchronizing) operation — the reference has the same property
        via lazy row-length caches (dataframe.py:242-343)."""
        from modin_tpu.ops.structural import pad_len
        from modin_tpu.parallel.engine import JaxWrapper

        if JaxWrapper.is_future(mask):
            # device-produced mask: fetch through the seam so the blocking
            # transfer gets the resilience policy (classify/retry/watchdog)
            mask = JaxWrapper.materialize(mask)
        mask_np = np.asarray(mask)
        n = len(self)
        if len(mask_np) == pad_len(n):
            # Device-produced masks carry shard padding; padded tail is dead.
            mask_np = mask_np[:n]
        elif len(mask_np) != n:
            raise ValueError(
                f"Item wrong length {len(mask_np)} instead of {n}."
            )
        positions = np.nonzero(mask_np)[0]
        return self._take_host_positions(positions)

    def filter_rows_mask_device(self, mask_raw: Any) -> "TpuDataframe":
        """Boolean-filter rows entirely on device (mask may be deferred).

        The mask computation fuses into the compaction kernel and the only
        host sync is the scalar kept-count; positions never round-trip
        through the host for device columns (the reference keeps lazy row
        counts for the same reason, ref dataframe.py:242-343).  Host columns
        and the row index resolve through one lazy positions fetch.
        """
        from modin_tpu.ops.structural import compact_rows
        from modin_tpu.parallel.engine import JaxWrapper

        from modin_tpu.ops.lazy import lazy_op
        from modin_tpu.ops.structural import pad_len

        device_idx = [i for i, c in enumerate(self._columns) if c.is_device]
        datas, count, perm = compact_rows(
            [self._columns[i].raw for i in device_idx], mask_raw, len(self)
        )
        n_out = int(JaxWrapper.materialize(count))
        # restore the padded-column invariant (physical size = pad_len(n)):
        # compaction kept the input's physical size, so trim to the output's.
        # The trim stays DEFERRED (one LazyExpr node per column): a consuming
        # reduction fuses it into its own program, so a filter->agg pipeline
        # costs two dispatches total (compact, fused trim+reduce) instead of
        # three; any other consumer batch-materializes the trims in one jit.
        p_out = pad_len(n_out)
        if datas and datas[0].shape[0] != p_out:
            datas = [
                lazy_op("trim", d, static=(("p_out", int(p_out)),)) for d in datas
            ]
        new_columns: List[Column] = list(self._columns)
        for i, d in zip(device_idx, datas):
            col = self._columns[i]
            new_columns[i] = DeviceColumn(d, col.pandas_dtype, length=n_out)

        host_positions_cache: dict = {}

        def host_positions() -> np.ndarray:
            if "pos" not in host_positions_cache:
                host_positions_cache["pos"] = np.asarray(
                    JaxWrapper.materialize(perm)
                )[:n_out]
            return host_positions_cache["pos"]

        for i, col in enumerate(self._columns):
            if not col.is_device:
                new_columns[i] = HostColumn(col.data.take(host_positions()))
        new_index = self._index.map_after(
            lambda idx: idx.take(host_positions()), n_out
        )
        return self.with_columns(new_columns, index=new_index, nrows=n_out)

    def concat_rows(self, others: List["TpuDataframe"]) -> "TpuDataframe":
        """Row-wise concat when column labels/dtypes align exactly."""
        from modin_tpu.ops.structural import concat_columns

        frames = [self, *others]
        for f in frames:
            f.materialize_device()
        lengths = [len(f) for f in frames]
        total = sum(lengths)
        device_ok = [
            all(f._columns[ci].is_device for f in frames)
            and len({f._columns[ci].data.dtype for f in frames}) == 1
            and _same_code_tables([f._columns[ci] for f in frames])
            for ci in range(self.num_cols)
        ]
        new_columns: List[Column] = [None] * self.num_cols
        device_cis = [ci for ci in range(self.num_cols) if device_ok[ci]]
        from modin_tpu import views as graftview

        if device_cis:
            parts = [[f._columns[ci].data for ci in device_cis] for f in frames]
            datas, n_out = concat_columns(parts, lengths)
            for ci, d in zip(device_cis, datas):
                cols = [f._columns[ci] for f in frames]
                # single read per column: eviction may race us
                caches = [c.host_cache for c in cols]
                cache = None
                if all(c is not None for c in caches):
                    cache = np.concatenate(caches)
                new_col = DeviceColumn(
                    d, cols[0].pandas_dtype, length=total, host_cache=cache
                )
                if graftview.VIEWS_ON:
                    # graftview append link: the new column's first
                    # len(self) rows ARE self's column — artifacts built
                    # from it fold only the appended tail on the next query
                    from modin_tpu.views import registry as views_registry

                    views_registry.note_append(new_col, cols[0])
                new_columns[ci] = new_col
        for ci in range(self.num_cols):
            if device_ok[ci]:
                continue
            if any(f._columns[ci].is_category for f in frames):
                # category columns whose code tables differ: pandas recodes
                # (or leaves object) through one decode of each
                merged = pandas.concat(
                    [
                        pandas.Series(f._columns[ci].to_pandas_array())
                        for f in frames
                    ],
                    ignore_index=True,
                ).array
                if isinstance(merged.dtype, pandas.CategoricalDtype):
                    new_columns[ci] = DeviceColumn.from_categorical(merged)
                else:
                    if isinstance(merged, pandas.arrays.NumpyExtensionArray):
                        merged = np.asarray(merged)
                    new_columns[ci] = HostColumn(merged)
                continue
            values = np.concatenate(
                [np.asarray(f._columns[ci].to_numpy()) for f in frames]
            )
            if all(f._columns[ci].is_device for f in frames):
                new_columns[ci] = DeviceColumn.from_numpy(values)
            else:
                dtypes = {f._columns[ci].pandas_dtype for f in frames}
                if len(dtypes) == 1:
                    # keep the exact dtype: re-inference would e.g. turn the
                    # pandas-3 'str' dtype into the 'string' extension dtype
                    arr = pandas.array(values, dtype=next(iter(dtypes)))
                else:
                    arr = pandas.array(values)
                if isinstance(arr, pandas.arrays.NumpyExtensionArray):
                    # store the raw ndarray, exactly like from_pandas: a
                    # NumpyEADtype('object') compares unequal to the
                    # np.dtype(object) every dispatch check expects, which
                    # would make a CHAINED concat fail the dtype-equality
                    # gate and fall back to pandas
                    arr = np.asarray(arr)
                new_columns[ci] = HostColumn(arr)
                if (
                    graftview.VIEWS_ON
                    and getattr(self._columns[ci], "_dict_cache", None)
                    not in (None, False)
                ):
                    # graftview dictionary maintenance: the prefix already
                    # paid its factorize — extend the code table with only
                    # the appended tail instead of re-encoding n_out rows
                    # on the next string groupby/nunique
                    from modin_tpu.views.incremental import extend_dict_encoding

                    ext = extend_dict_encoding(
                        self._columns[ci], values[lengths[0]:]
                    )
                    if ext is not None:
                        new_columns[ci]._dict_cache = ext
                        from modin_tpu.logging.metrics import emit_metric

                        emit_metric("view.fold", 1)
        lazies = [f._index for f in frames]

        def build_index() -> pandas.Index:
            return lazies[0].get().append([lz.get() for lz in lazies[1:]])

        return self.with_columns(
            new_columns, index=LazyIndex(build_index, total), nrows=total
        )

    def get_column(self, position: int) -> Column:
        return self._columns[position]

    @disable_logging
    def column_position(self, label: Any) -> List[int]:
        return list(self._col_labels.get_indexer_for([label]))
