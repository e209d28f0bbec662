"""Window planning & parsing for the streaming executor.

A :class:`WindowSource` wraps one CSV-family source file as a sequence of
record-aligned byte-range windows, reusing the byte-range machinery the
parallel readers already own (``core/io/chunker.py``): ``find_header_end``
locates the header, ``split_record_ranges`` cuts the body at record
boundaries near the window-byte target, and each window parses exactly like
one of ``_read_parallel``'s body chunks (``header=None`` + the full column
``names`` learned once from the header, so ``usecols`` projection — including
graftplan's pushed pruning — applies per window).

Window sizing: ``MODIN_TPU_STREAM_WINDOW_BYTES`` when set, else derived from
the device budget so ``1 + prefetch`` resident windows plus a 2x kernel
working-set allowance fit under it by construction:
``budget // (2 * (1 + prefetch))``.
"""

from __future__ import annotations

import io
from typing import Any, List, Optional, Tuple

import pandas

from modin_tpu.core.io.chunker import find_header_end, split_record_ranges
from modin_tpu.logging.metrics import emit_metric

#: floor on the derived window size: below this the per-window parse and
#: dispatch overheads dominate any budget benefit (budgets tighter than
#: the floor can honor still stream, best-effort, at this granularity)
_MIN_WINDOW_BYTES = 1 << 16

#: parsed-device-bytes per source-byte bound for numeric CSV text: every
#: device-eligible value is <= 8 bytes parsed and >= 2 bytes of text
#: ("0," / "0\n"), so device bytes <= 4x the window's source bytes —
#: object/string columns stay host-side and never count against HBM
_PARSE_EXPANSION = 4

#: kwargs that never reach a body-chunk parse (mirrors _read_parallel)
_BODY_DROP = ("iterator", "chunksize", "skiprows", "nrows")


def window_bytes_for(prefetch: int) -> int:
    """The source-byte window target for the current budget/knobs.

    Derivation keeps peak device residency under budget by construction:
    ``1 + prefetch`` windows are resident at once, each claiming at most
    ``_PARSE_EXPANSION`` device bytes per source byte, with a 2x allowance
    for the consuming kernel's working set (masks, compacted copies).
    """
    from modin_tpu.config import DeviceMemoryBudget, StreamWindowBytes

    explicit = int(StreamWindowBytes.get())
    if explicit > 0:
        return max(explicit, 1)
    budget = DeviceMemoryBudget.get()
    if budget is None:
        return _MIN_WINDOW_BYTES
    windows_resident = 1 + max(int(prefetch), 0)
    return max(
        budget // (2 * _PARSE_EXPANSION * windows_resident),
        _MIN_WINDOW_BYTES,
    )


def streamable_read_kwargs(dispatcher: type, kwargs: dict) -> Optional[dict]:
    """The normalized reader kwargs when this read can stream, else None.

    Streaming shares the parallel reader's eligibility: a local plain file
    whose kwargs the record-aligned chunker can honor exactly
    (``_can_parallelize``).  Anything else stays on the resident path.
    """
    can = getattr(dispatcher, "_can_parallelize", None)
    if can is None or getattr(dispatcher, "read_fn", None) is None:
        return None
    kwargs = dispatcher.normalize_read_kwargs(dict(kwargs))
    path = kwargs.get("filepath_or_buffer")
    if not dispatcher.is_local_plain_file(path):
        return None
    if not can(kwargs):
        return None
    return kwargs


class WindowSource:
    """Record-aligned byte-range windows over one CSV-family source."""

    def __init__(self, dispatcher: type, read_kwargs: dict, window_bytes: int):
        self.dispatcher = dispatcher
        self.read_kwargs = dict(read_kwargs)
        path = dispatcher.get_path(read_kwargs["filepath_or_buffer"])
        self.path = path
        # mmap, not a read(): planning a 10 GB source touches a few pages
        self.buf = dispatcher.read_file_bytes(path)
        quotechar = read_kwargs.get("quotechar") or '"'
        skiprows = int(read_kwargs.get("skiprows") or 0)
        header_rows = 1  # header='infer' with names=None (gated upstream)
        header_end = find_header_end(self.buf, skiprows + header_rows, quotechar)
        header_bytes = bytes(self.buf[:header_end])
        head_kwargs = {
            k: v
            for k, v in read_kwargs.items()
            if k not in _BODY_DROP and k != "filepath_or_buffer"
        }
        # the FULL (pre-usecols) column list, learned once: body chunks
        # need it as positional names so usecols filters per window exactly
        # like it filters a whole-file parse
        name_kwargs = {k: v for k, v in head_kwargs.items() if k != "usecols"}
        self.full_columns = dispatcher.read_fn(
            io.BytesIO(header_bytes), skiprows=skiprows, nrows=0, **name_kwargs
        ).columns
        self.body_kwargs = dict(head_kwargs)
        self.body_kwargs["header"] = None
        self.body_kwargs["names"] = self.full_columns
        self._header_bytes = header_bytes
        self._head_kwargs = head_kwargs
        self._skiprows = skiprows
        self.ranges: List[Tuple[int, int]] = split_record_ranges(
            self.buf, header_end, max(int(window_bytes), 1), quotechar
        )

    def __len__(self) -> int:
        return len(self.ranges)

    def empty_frame(self) -> pandas.DataFrame:
        """The zero-row frame of this source (header-only parse): the
        window chain runs over it once when the body is empty, so an empty
        streamed source answers exactly like an empty resident read."""
        return self.dispatcher.read_fn(
            io.BytesIO(self._header_bytes),
            skiprows=self._skiprows,
            **self._head_kwargs,
        )

    def parse_window(self, index: int) -> Any:
        """Parse window ``index`` into an eager query compiler.

        Device uploads ride the engine seam (resilience retry, graftguard
        host lineage, ledger admission) like any other ingest, but the
        physical row shape is padded to a **power-of-two bucket** instead
        of the window's exact ragged row count: record-aligned byte ranges
        give every window a slightly different length, and without
        bucketing each one would compile a fresh XLA program for the whole
        consuming chain — with it, every same-bucket window re-dispatches
        the first one's executables.  The caller owns releasing the window.
        """
        start, end = self.ranges[index]
        df = self.dispatcher.read_fn(
            io.BytesIO(bytes(self.buf[start:end])), **self.body_kwargs
        )
        emit_metric("stream.window.bytes", end - start)
        emit_metric("stream.window.rows", len(df))
        return self._qc_from_window(df)

    def _qc_from_window(self, df: pandas.DataFrame) -> Any:
        """``from_pandas`` with bucketed physical padding (see above):
        device-eligible columns upload at ``pad_len(bucket)`` rows with the
        real row count as the logical length — pad rows are dead by the
        same masking contract every kernel already honors."""
        import numpy as np

        m = len(df)
        columns = []
        for i in range(df.shape[1]):
            series = df.iloc[:, i]
            dtype = series.dtype
            if isinstance(dtype, np.dtype):
                columns.append(bucketed_column(series.to_numpy(), m))
            else:
                arr = series.array.copy()
                if isinstance(arr, pandas.arrays.NumpyExtensionArray):
                    arr = np.asarray(arr)
                from modin_tpu.core.dataframe.tpu.dataframe import HostColumn

                columns.append(HostColumn(arr))
        frame = self.dispatcher.frame_cls(
            columns, df.columns, df.index, nrows=m
        )
        return self.dispatcher.query_compiler_cls(frame)


def pow2_bucket(m: int) -> int:
    """Power-of-two row bucket (floor 1024) a window pads its physical
    shape to, so every same-bucket window re-dispatches the first one's
    compiled programs instead of re-tracing for its exact ragged length.
    When the compile ledger reports the fused window programs themselves
    storming (graftfuse storm feedback), the bucket coarsens one level so
    near-boundary window streams collapse onto fewer executables."""
    bucket = max(1 << max(m - 1, 1).bit_length(), 1024)
    try:
        from modin_tpu.plan.fuse import stream_bucket

        return max(bucket, stream_bucket(bucket))
    except Exception:
        # the coarsening consult is an optimization; any import/plan
        # failure keeps the plain pow2 bucket
        return bucket


def bucketed_column(values: Any, m: int) -> Any:
    """One window column: device upload padded to ``pow2_bucket(m)`` with
    logical length ``m`` (exact host copy kept for lineage/fallbacks), or a
    HostColumn when the dtype is not device-eligible or the upload fails."""
    import numpy as np

    from modin_tpu.core.dataframe.tpu.dataframe import (
        DeviceColumn,
        HostColumn,
        _device_layout_values,
        _is_device_dtype,
    )
    from modin_tpu.core.execution.resilience import DeviceFailure
    from modin_tpu.ops.structural import pad_host
    from modin_tpu.parallel.engine import JaxWrapper

    values = np.asarray(values)
    if not _is_device_dtype(values.dtype):
        return HostColumn(values)
    try:
        data = JaxWrapper.put(
            pad_host(
                np.ascontiguousarray(_device_layout_values(values)),
                pow2_bucket(m),
            )
        )
    except DeviceFailure:
        # mirror from_pandas: a failed upload degrades the column to host
        # instead of killing the window
        return HostColumn(values)
    return DeviceColumn(data, values.dtype, length=m, host_cache=values)


def release_qc(qc: Any) -> None:
    """Drop a consumed window's device buffers immediately.

    Ledger entries are weakref-backed, so waiting for GC would let dead
    windows count against the budget (and against the smoke's peak-resident
    assertion) until an arbitrary collection pass; deregistering here makes
    "consume -> drop" a real edge.  The post-drop residency gauge is
    emitted so meter snapshots carry the between-window footprint.
    """
    from modin_tpu.core.memory import device_ledger, ledger

    frame = getattr(qc, "_frame", None)
    if frame is None:
        return
    for col in getattr(frame, "_columns", ()):
        if getattr(col, "is_device", False):
            col._invalidate_sorted()
            device_ledger.deregister(col)
            col._data = None
            col.host_cache = None
    frame.free()
    emit_metric("memory.device.resident_bytes", device_ledger.total_bytes())
    emit_metric("memory.host.cache_bytes", ledger.total_bytes())


def host_values(col: Any):
    """A column's exact host values: the spilled/ingest host copy when it
    exists (an out-of-core column's only copy), the seam-fetched device
    buffer otherwise.  The ONE such helper for the streaming package."""
    import numpy as np

    cache = col.host_cache
    if cache is not None:
        return np.asarray(cache)
    # the buffer's rows as the device holds them (a category column's codes,
    # like its host copy), so that callers can re-wrap them under the dtype
    return col.buffer_to_numpy()


def frame_nbytes(frame: Any) -> int:
    """Logical bytes of a frame's columns (device padded size where
    concrete, host array size otherwise) — the residency-router estimate."""
    total = 0
    for col in getattr(frame, "_columns", ()):
        if getattr(col, "is_device", False):
            data = col._data
            nbytes = getattr(data, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
            elif col.host_cache is not None:
                total += int(col.host_cache.nbytes)
            else:
                total += int(col.length) * col.pandas_dtype.itemsize
        else:
            total += int(getattr(col.data, "nbytes", 0) or 0)
    return total


def frame_resident_bytes(frame: Any) -> int:
    """The share of ``frame_nbytes`` currently concrete on device (spilled
    and lazy columns contribute nothing) — subtracted from the ledger total
    when computing the residency headroom, so a frame is not double-counted
    against itself."""
    total = 0
    for col in getattr(frame, "_columns", ()):
        if getattr(col, "is_device", False) and not col.is_lazy:
            nbytes = getattr(col._data, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
    return total
