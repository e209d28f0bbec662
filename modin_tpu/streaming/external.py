"""External (out-of-core) sort and the spill-aware sort-merge join.

Classic external sort, mapped onto the repo's seams: the key column is
deployed and sorted **one window at a time** with the same device kernel the
resident path uses (``ops/sort.lexsort_permutation`` — identical comparator:
IEEE total order, NaN past +inf, na_position='last' both directions), each
window's sorted (merge-key, global-row-id) pair is spilled to host as a
sorted **run**, and the runs fold through a stable vectorized k-way merge
(binary merge tree of ``searchsorted`` passes, O(n log k), earlier windows
win ties — exactly a global stable sort).  Payload columns never touch the
device: the final permutation gathers them on host, and the output frame is
built from **spilled-by-birth** device columns (``_data=None`` + exact
``host_cache``) that restore on demand — an out-of-core result never claims
more HBM than its consumer actually touches.

The merge-join reuses the same machinery as its build phase: the right
side's key is externally sorted (sorted runs streamed from host), the left
side probes it window by window with the resident kernel's own
lo/hi-``searchsorted`` + expand arithmetic, and both sides' columns gather
by the resulting positions.  Output rows match pandas ``merge`` for
``sort=False`` — left order, right ties in right's original order — because
the stable external sort preserves original order within equal keys just
like the resident stable device sort does.

Both entry points return ``None`` whenever any gate fails and the caller
falls through to the resident path: the router (``decide_residency``)
chooses the residency, these kernels only decline what they cannot
reproduce bit-exactly.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np
import pandas

from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import spans as graftscope
from modin_tpu.streaming import window_body
from modin_tpu.streaming import windows as _windows

_I64 = np.iinfo(np.int64)


# ---------------------------------------------------------------------- #
# merge keys: the host mirror of the device comparator
# ---------------------------------------------------------------------- #


def _total_order_np(x: np.ndarray) -> np.ndarray:
    """numpy mirror of ``ops/structural.float_total_order`` — monotone
    float64 -> int64, -0.0 == 0.0, every NaN canonicalized to ONE key past
    +inf.  Byte-for-byte the ordering the device sort kernels apply."""
    x = np.where(x == 0, 0.0, x)
    x = np.where(np.isnan(x), np.nan, x)  # canonicalize NaN sign/payload
    bits = np.ascontiguousarray(np.asarray(x, np.float64)).view(np.int64)
    return np.where(bits >= 0, bits, (~bits) ^ np.int64(-(2 ** 63)))


def _merge_key(vals: np.ndarray, ascending: bool) -> np.ndarray:
    """int64 keys whose ASCENDING order reproduces the device lexsort's
    row order for ``na_position='last'`` in either direction (descending
    maps NaN to the device kernel's int64.min+1 slot, then bit-complements
    — the stable-order-preserving reversal)."""
    if vals.dtype.kind == "f":
        t = _total_order_np(vals.astype(np.float64, copy=False))
        if ascending:
            return t  # NaN's total-order key already sorts past +inf
        return ~np.where(np.isnan(vals), np.int64(_I64.min + 1), t)
    v = vals.astype(np.int64, copy=False)
    return v if ascending else ~v


def _merge_runs(
    a: Tuple[np.ndarray, np.ndarray], b: Tuple[np.ndarray, np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Stable merge of two sorted (key, row-id) runs; ``a`` (the earlier
    windows) wins ties."""
    ka, ia = a
    kb, ib = b
    pos_a = np.arange(ka.size, dtype=np.int64) + np.searchsorted(
        kb, ka, side="left"
    )
    pos_b = np.arange(kb.size, dtype=np.int64) + np.searchsorted(
        ka, kb, side="right"
    )
    keys = np.empty(ka.size + kb.size, dtype=ka.dtype)
    ids = np.empty(ka.size + kb.size, dtype=np.int64)
    keys[pos_a] = ka
    keys[pos_b] = kb
    ids[pos_a] = ia
    ids[pos_b] = ib
    return keys, ids


def _fold_runs(
    runs: List[Tuple[np.ndarray, np.ndarray]]
) -> Tuple[np.ndarray, np.ndarray]:
    """Binary merge tree over window-ordered runs (stability: the left
    operand is always the earlier windows)."""
    with graftscope.span("stream.merge", layer="QUERY-COMPILER", runs=len(runs)):
        while len(runs) > 1:
            merged = []
            for j in range(0, len(runs), 2):
                if j + 1 < len(runs):
                    merged.append(_merge_runs(runs[j], runs[j + 1]))
                else:
                    merged.append(runs[j])
            runs = merged
    return runs[0]


# ---------------------------------------------------------------------- #
# sorted-run production (the per-window device sort)
# ---------------------------------------------------------------------- #


def _host_values(col: Any) -> np.ndarray:
    """Shared exact-host-values fetch (modin_tpu/streaming/windows.py)."""
    return _windows.host_values(col)


def _downcast_blocks(frame: Any) -> bool:
    """Under Float64Policy=Downcast the resident kernels compare/gather f32
    device buffers while the external path reads exact f64 host copies —
    bit-exact parity with the resident output is impossible, so decline."""
    from modin_tpu.config import Float64Policy

    if Float64Policy.get() != "Downcast":
        return False
    return any(
        getattr(c, "is_device", False) and c.pandas_dtype == np.float64
        for c in frame._columns
    )


def _sort_runs(
    values: np.ndarray, n: int, ascending: bool, window_rows: int
) -> Tuple[np.ndarray, np.ndarray]:
    """External sort of one key column: per-window DEVICE sort -> spilled
    sorted (merge-key, global-row-id) runs -> k-way fold.  Returns the
    fully merged (keys, permutation) pair."""
    from modin_tpu.core.dataframe.tpu.dataframe import _device_layout_values
    from modin_tpu.ops.sort import lexsort_permutation
    from modin_tpu.ops.structural import pad_host
    from modin_tpu.parallel.engine import JaxWrapper
    from modin_tpu.parallel.engine import materialize as _engine_materialize

    runs: List[Tuple[np.ndarray, np.ndarray]] = []

    @window_body
    def _one_window(start: int, stop: int) -> None:
        # the SAME host->device transform a resident upload applies, so the
        # device kernel compares exactly what it would compare resident
        layout = _device_layout_values(
            np.ascontiguousarray(values[start:stop])
        )
        wlen = stop - start
        dev = JaxWrapper.put(pad_host(layout))
        perm = lexsort_permutation([dev], wlen, [ascending])
        perm_h = np.asarray(_engine_materialize(perm))[:wlen].astype(np.int64)
        del dev, perm  # drop the window's device buffers before the next
        sorted_vals = layout[perm_h]
        run = (_merge_key(sorted_vals, ascending), start + perm_h)
        emit_metric(
            "stream.spill.run_bytes", run[0].nbytes + run[1].nbytes
        )
        runs.append(run)

    for start in range(0, n, window_rows):
        stop = min(start + window_rows, n)
        with graftscope.span(
            "stream.window", layer="QUERY-COMPILER", window=len(runs)
        ):
            _one_window(start, stop)
        emit_metric("stream.window.count", 1)
        emit_metric("stream.window.rows", stop - start)
    return _fold_runs(runs)


def _sort_window_rows(itemsize: int = 8) -> int:
    """Rows per sort window: the key window plus the kernel's perm/working
    buffers must fit the streaming window budget."""
    from modin_tpu.config import StreamPrefetch

    window_bytes = _windows.window_bytes_for(int(StreamPrefetch.get()))
    return max(window_bytes // (2 * max(itemsize, 1)), 1024)


# ---------------------------------------------------------------------- #
# external sort_values
# ---------------------------------------------------------------------- #


def external_sort_qc(
    qc: Any, columns: Any, ascending: Any, kwargs: dict
) -> Optional[Any]:
    """Out-of-core ``sort_values``: bit-identical to the resident device
    sort path, or None when a gate fails (the resident path then runs)."""
    from modin_tpu.core.dataframe.tpu.dataframe import (
        DeviceColumn,
        HostColumn,
        TpuDataframe,
    )
    from modin_tpu.core.dataframe.tpu.metadata import LazyIndex

    if kwargs.get("na_position", "last") != "last" or kwargs.get("key") is not None:
        return None
    col_list = [columns] if not isinstance(columns, (list, tuple)) else list(columns)
    if len(col_list) != 1:
        return None  # multi-key external merge needs composite keys: resident
    asc = ascending[0] if isinstance(ascending, (list, tuple)) else ascending
    frame = qc._modin_frame
    n = len(frame)
    if n == 0 or not frame.columns.is_unique:
        return None
    pos = frame.column_position(col_list[0])
    if len(pos) != 1 or pos[0] < 0:
        return None
    key_col = frame._columns[pos[0]]
    if (
        not getattr(key_col, "is_device", False)
        or key_col.pandas_dtype.kind not in "biuf"
        or key_col.pandas_dtype == np.uint64  # int64 merge keys would wrap
        or key_col.is_lazy
    ):
        return None
    if _downcast_blocks(frame):
        return None
    for c in frame._columns:
        if not getattr(c, "is_device", False) and not hasattr(c.data, "take"):
            return None
        if getattr(c, "is_device", False) and c.is_lazy:
            return None
    window_rows = _sort_window_rows(key_col.pandas_dtype.itemsize)
    if n <= window_rows:
        return None  # one window IS the resident sort: let it run resident

    key_values = _host_values(key_col)
    _keys, perm = _sort_runs(key_values, n, bool(asc), window_rows)

    new_cols: list = []
    for c in frame._columns:
        if getattr(c, "is_device", False):
            vals = np.ascontiguousarray(_host_values(c)[perm])
            # spilled-by-birth: the exact host copy is the only copy until
            # a device consumer restores it — an out-of-core result must
            # not re-claim dataset-sized HBM just by existing
            new_cols.append(
                DeviceColumn(None, c.pandas_dtype, length=n, host_cache=vals)
            )
        else:
            new_cols.append(HostColumn(c.data.take(perm)))
    if kwargs.get("ignore_index", False):
        new_index = LazyIndex(pandas.RangeIndex(n), n)
    else:
        lazy = frame._index
        new_index = LazyIndex(lambda: lazy.get().take(perm), n)
    return type(qc)(TpuDataframe(new_cols, frame.columns, new_index, nrows=n))


# ---------------------------------------------------------------------- #
# spill-aware merge-join
# ---------------------------------------------------------------------- #


def external_merge_qc(qc: Any, right: Any, kwargs: dict) -> Optional[Any]:
    """Out-of-core sort-merge join: the right (build) side's key externally
    sorts into host runs, the left side probes them window by window, and
    the output gathers on host into spilled-by-birth columns.  Bit-identical
    to the resident device merge (pandas ``merge`` row order for
    ``sort=False``); None when a gate fails."""
    from modin_tpu.core.dataframe.tpu.dataframe import (
        DeviceColumn,
        HostColumn,
        TpuDataframe,
    )
    from modin_tpu.core.dataframe.tpu.metadata import LazyIndex
    from modin_tpu.utils import hashable

    how = kwargs.get("how", "inner")
    if how not in ("inner", "left"):
        return None
    if (
        kwargs.get("left_index")
        or kwargs.get("right_index")
        or kwargs.get("sort")
        or kwargs.get("indicator")
        or kwargs.get("validate") is not None
        or not isinstance(right, type(qc))
    ):
        return None
    on = kwargs.get("on")
    left_on, right_on = kwargs.get("left_on"), kwargs.get("right_on")
    if on is not None:
        if isinstance(on, list):
            if len(on) != 1:
                return None
            on = on[0]
        l_label = r_label = on
    elif left_on is not None and right_on is not None:
        def _single(x):
            if isinstance(x, list):
                return x[0] if len(x) == 1 else None
            return x

        l_label, r_label = _single(left_on), _single(right_on)
        if l_label is None or r_label is None:
            return None
    else:
        return None
    if not hashable(l_label) or not hashable(r_label):
        return None
    coalesce = l_label == r_label

    lframe, rframe = qc._modin_frame, right._modin_frame
    if not lframe.columns.is_unique or not rframe.columns.is_unique:
        return None
    if len(lframe) == 0 or len(rframe) == 0:
        return None
    lp = lframe.column_position(l_label)
    rp = rframe.column_position(r_label)
    if len(lp) != 1 or lp[0] < 0 or len(rp) != 1 or rp[0] < 0:
        return None
    lkey_col, rkey_col = lframe._columns[lp[0]], rframe._columns[rp[0]]
    for kc in (lkey_col, rkey_col):
        if (
            not getattr(kc, "is_device", False)
            or kc.pandas_dtype.kind not in "biuf"
            or kc.pandas_dtype == np.uint64
            or kc.is_lazy
        ):
            return None
    if lkey_col.pandas_dtype != rkey_col.pandas_dtype:
        return None  # pandas promotes mixed-width keys: resident/fallback
    if _downcast_blocks(lframe) or _downcast_blocks(rframe):
        return None
    # no suffix logic here: any non-key label collision declines
    l_labels = list(lframe.columns)
    r_labels = list(rframe.columns)
    r_out_positions = [
        i
        for i in range(rframe.num_cols)
        if not (coalesce and i == rp[0])
    ]
    overlap = set(l_labels) & {r_labels[i] for i in r_out_positions}
    if overlap:
        return None
    object_like = (
        lambda c: pandas.api.types.is_object_dtype(c.pandas_dtype)
        or isinstance(c.pandas_dtype, pandas.StringDtype)
    )
    for fr in (lframe, rframe):
        for c in fr._columns:
            if getattr(c, "is_category", False):
                return None  # as the resident merge: pandas answers
            if getattr(c, "is_device", False):
                if c.is_lazy:
                    return None
            elif not object_like(c):
                return None
    if how == "left" and any(
        rframe._columns[i].pandas_dtype.kind == "b"
        and getattr(rframe._columns[i], "is_device", False)
        for i in r_out_positions
    ):
        return None  # null-side bool becomes object in pandas: fallback

    n_left, n_right = len(lframe), len(rframe)
    window_rows = _sort_window_rows(rkey_col.pandas_dtype.itemsize)
    if max(n_left, n_right) <= window_rows:
        return None  # fits one window: the resident kernels win

    # ---- build side: externally sorted right key runs ----------------- #
    r_keys_sorted, r_ids_sorted = _sort_runs(
        _host_values(rkey_col), n_right, True, window_rows
    )

    # ---- probe side: window-wise searchsorted + expand ----------------- #
    l_values = _host_values(lkey_col)
    left_parts: List[np.ndarray] = []
    right_parts: List[np.ndarray] = []

    @window_body
    def _probe_window(start: int, stop: int) -> None:
        lk = _merge_key(
            np.ascontiguousarray(l_values[start:stop]), True
        )
        lo = np.searchsorted(r_keys_sorted, lk, side="left")
        hi = np.searchsorted(r_keys_sorted, lk, side="right")
        counts = hi - lo
        emit = np.maximum(counts, 1) if how == "left" else counts
        total = int(emit.sum())
        if total == 0:
            return
        ends = np.cumsum(emit)
        out = np.arange(total, dtype=np.int64)
        left_idx = np.searchsorted(ends, out, side="right")
        within = out - (ends - emit)[left_idx]
        sorted_pos = lo[left_idx] + within
        right_rows = r_ids_sorted[np.minimum(sorted_pos, r_ids_sorted.size - 1)]
        if how == "left":
            right_rows = np.where(counts[left_idx] > 0, right_rows, -1)
        left_parts.append(start + left_idx)
        right_parts.append(right_rows)

    for start in range(0, n_left, window_rows):
        _probe_window(start, min(start + window_rows, n_left))
    if left_parts:
        left_pos = np.concatenate(left_parts)
        right_pos = np.concatenate(right_parts)
    else:
        left_pos = np.empty(0, np.int64)
        right_pos = np.empty(0, np.int64)
    n_out = left_pos.size
    has_miss = bool(n_out) and bool((right_pos < 0).any())

    # ---- gather + assemble -------------------------------------------- #
    def _host_gather(col: Any, positions: np.ndarray) -> Any:
        values = col.data
        if (positions >= 0).all():
            # all positions valid (every left column; right columns of an
            # inner join): a plain take preserves the array dtype —
            # StringDtype columns must stay StringDtype, as the resident
            # merge keeps them
            return values.take(positions)
        # miss-capable gather works on an object array, then tries to
        # restore the original dtype (the resident path's
        # _restore_host_dtype contract: a strict extension dtype that
        # rejects the join-introduced NaNs keeps the object array, matching
        # pandas' merge upcasting)
        vals = np.asarray(values, dtype=object)
        out = np.empty(positions.size, dtype=object)
        valid = positions >= 0
        out[valid] = vals[positions[valid]]
        out[~valid] = np.nan
        dtype = col.pandas_dtype
        if pandas.api.types.is_object_dtype(dtype):
            return out
        try:
            return pandas.array(out, dtype=dtype)
        except (TypeError, ValueError):
            return out

    new_cols: list = []
    labels: list = []
    for i, c in enumerate(lframe._columns):
        labels.append(l_labels[i])
        if getattr(c, "is_device", False):
            vals = np.ascontiguousarray(_host_values(c)[left_pos])
            new_cols.append(
                DeviceColumn(
                    None, c.pandas_dtype, length=n_out, host_cache=vals
                )
            )
        else:
            new_cols.append(HostColumn(_host_gather(c, left_pos)))
    safe_right = np.where(right_pos >= 0, right_pos, 0)
    miss = right_pos < 0
    for i in r_out_positions:
        c = rframe._columns[i]
        labels.append(r_labels[i])
        if getattr(c, "is_device", False):
            vals = _host_values(c)[safe_right]
            if has_miss:
                kind = c.pandas_dtype.kind
                if kind == "f":
                    vals = vals.copy()
                    vals[miss] = np.nan
                elif kind in "mM":
                    vals = vals.copy()
                    vals[miss] = np.datetime64("NaT") if kind == "M" else (
                        np.timedelta64("NaT")
                    )
                else:  # int/uint promote to float64 + NaN, as pandas does
                    vals = vals.astype(np.float64)
                    vals[miss] = np.nan
            vals = np.ascontiguousarray(vals)
            new_cols.append(
                DeviceColumn(
                    None, vals.dtype, length=n_out, host_cache=vals
                )
            )
        else:
            new_cols.append(HostColumn(_host_gather(c, right_pos)))
    index = LazyIndex(pandas.RangeIndex(n_out), n_out)
    return type(qc)(
        TpuDataframe(new_cols, pandas.Index(labels), index, nrows=n_out)
    )
