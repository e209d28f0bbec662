"""``FileDispatcher`` — shared path handling + the read template.

Reference design: /root/reference/modin/core/io/file_dispatcher.py:116: path
normalization/validation and the ``read -> _read`` template each format
dispatcher fills in.  fsspec is used when available (S3/GCS paths), plain
filesystem otherwise.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Optional

from modin_tpu.logging import ClassLogger
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import meters as graftmeter
from modin_tpu.observability import spans as graftscope

NOT_IMPLEMENTED_MESSAGE = "Implement in children classes!"


class _IoReplay:
    """Re-run a dispatcher read and serve per-column exact host values.

    The io-source lineage record (core/execution/recovery.py): holds only
    the dispatcher class and the original call args — no data — and on
    demand re-reads the source once per device epoch, memoizing the host
    values so a recovery pass re-seating N columns costs one read, not N.
    Recovered columns adopt the memoized arrays as ``host_cache``; the memo
    itself is dropped at the end of every recovery pass (``drop_cache``,
    called via the recovery manager's purge hook) so one pass never pins a
    full host copy of the source dataset beyond its own duration.
    """

    def __init__(self, dispatcher: type, args: tuple, kwargs: dict):
        self._dispatcher = dispatcher
        self._args = args
        self._kwargs = kwargs
        self._cache: Optional[tuple] = None  # (epoch, [values per position])

    def drop_cache(self) -> None:
        self._cache = None

    def values_for(self, pos: int) -> Any:
        from modin_tpu.core.execution import recovery

        epoch = recovery.current_epoch()
        cache = self._cache
        if cache is None or cache[0] != epoch:
            result = self._dispatcher._read(*self._args, **self._kwargs)
            frame = getattr(result, "_modin_frame", None)
            columns = getattr(frame, "_columns", None)
            if columns is None:
                raise RuntimeError(
                    f"{self._dispatcher.__name__} re-read produced no frame"
                )
            cache = (
                epoch,
                # the buffers' own rows (a category column's codes)
                [c.buffer_to_numpy() if c.is_device else None for c in columns],
            )
            self._cache = cache
            recovery.note_io_replayer(self)  # purged at end of pass
        values = cache[1][pos] if pos < len(cache[1]) else None
        if values is None:
            raise RuntimeError(
                f"column {pos} absent from the {self._dispatcher.__name__} re-read"
            )
        return values


class FileDispatcher(ClassLogger, modin_layer="CORE-IO"):
    query_compiler_cls = None
    frame_cls = None

    @classmethod
    def read(cls, *args: Any, **kwargs: Any):
        """Template: normalize, dispatch to _read, postprocess.

        Under the ``TrackFileLeaks`` config every read is audited for leaked
        file descriptors (reference guard: modin/config/envvars.py:893).

        Every device column of the result gets an **io-source lineage
        record** (graftguard): if the device is lost — even after the
        column's host cache was evicted under the ``Memory`` budget — the
        recovery manager can rebuild it by re-running this read.
        """
        from modin_tpu.utils.file_leaks import track_file_leaks

        with graftscope.span("io.read", layer="CORE-IO", dispatcher=cls.__name__):
            with track_file_leaks():
                result = cls._read(*args, **kwargs)
        if graftmeter.ACCOUNTING_ON:
            cls._note_read_bytes(args, kwargs)
        cls._attach_io_lineage(result, args, kwargs)
        return result

    @classmethod
    def _note_read_bytes(cls, args: tuple, kwargs: dict) -> None:
        """Bill this read's source bytes to graftmeter (best-effort)."""
        try:
            path = kwargs.get("filepath_or_buffer") or kwargs.get("path") or (
                args[0] if args else None
            )
            if isinstance(path, str):
                path = cls.get_path(path)
            if cls.is_local_plain_file(path):
                emit_metric("io.read.bytes", cls.file_size(path))
        except Exception:  # graftlint: disable=EXC-HYGIENE -- byte accounting is best-effort; an exotic path simply goes unbilled
            pass

    @classmethod
    def _attach_io_lineage(cls, result: Any, args: tuple, kwargs: dict) -> None:
        from modin_tpu.core.execution import recovery

        if not recovery.RECOVERY_ON:
            return
        try:
            frame = getattr(result, "_modin_frame", None)
            columns = getattr(frame, "_columns", None)
            if not columns:
                return
            replayer = _IoReplay(cls, args, kwargs)
            for pos, col in enumerate(columns):
                if getattr(col, "is_device", False):
                    recovery.attach_io_lineage(
                        col,
                        replay=functools.partial(replayer.values_for, pos),
                        detail=cls.__name__,
                    )
        except Exception:  # graftlint: disable=EXC-HYGIENE -- lineage attachment is best-effort; a read result without the expected frame shape just keeps its host/op lineage
            pass

    @classmethod
    def _read(cls, *args: Any, **kwargs: Any):
        raise NotImplementedError(NOT_IMPLEMENTED_MESSAGE)

    # ---- shared parallel-read template (text dispatchers) ------------- #

    MIN_PARALLEL_BYTES = 8 << 20  # below this a single parse wins

    @classmethod
    def _read_gated(cls, raw_path: Any, path_key: str, kwargs: dict):
        """Route to _read_parallel when the chunked path applies, else the
        serial fallback; any parallel-path error degrades to the fallback
        (correct, just serial)."""
        path = cls.get_path(raw_path) if isinstance(raw_path, str) else raw_path
        if (
            not cls.is_local_plain_file(path)
            or not cls._can_parallelize({**kwargs, path_key: path})
            or cls.file_size(path) < cls.MIN_PARALLEL_BYTES
        ):
            return cls._read_fallback(path, kwargs)
        try:
            return cls._read_parallel(path, kwargs)
        except Exception:  # graftlint: disable=EXC-HYGIENE -- fsspec/credential probing; a failed probe means 'not readable here'
            return cls._read_fallback(path, kwargs)

    @classmethod
    def _parse_ranges_threaded(cls, ranges: list, parse) -> list:
        """Parse record-aligned byte ranges on a thread pool (the pandas C
        parsers release the GIL)."""
        from concurrent.futures import ThreadPoolExecutor

        from modin_tpu.config import CpuCount

        if len(ranges) == 1:
            return [parse(ranges[0])]
        with ThreadPoolExecutor(
            max_workers=min(CpuCount.get(), len(ranges))
        ) as pool:
            return list(pool.map(parse, ranges))

    @classmethod
    def get_path(cls, file_path: str) -> str:
        if isinstance(file_path, str) and file_path.startswith("~"):
            return os.path.expanduser(file_path)
        return file_path

    @classmethod
    def normalize_read_kwargs(cls, kwargs: dict) -> dict:
        """Canonicalize reader kwargs (e.g. default separators) so the
        eager read and graftplan's deferred Scan agree on one source of
        truth.  Subclasses override; the base is the identity."""
        return kwargs

    @classmethod
    def is_local_plain_file(cls, path: Any) -> bool:
        """Whether the path is a plain local uncompressed file we can mmap."""
        if not isinstance(path, (str, os.PathLike)):
            return False
        p = os.fspath(path)
        if "://" in p and not p.startswith("file://"):
            return False
        p = p.removeprefix("file://")
        p = os.path.expanduser(p)
        return os.path.isfile(p)

    @classmethod
    def file_size(cls, path: str) -> int:
        return os.path.getsize(os.path.expanduser(os.fspath(path).removeprefix("file://")))

    @classmethod
    def read_file_bytes(cls, path: str) -> bytes:
        import mmap

        p = os.path.expanduser(os.fspath(path).removeprefix("file://"))
        with open(p, "rb") as f:
            try:
                return mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
            except (ValueError, OSError):  # empty file or mmap unsupported
                return f.read()
