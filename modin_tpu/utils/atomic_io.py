"""Crash-safe file writes: ONE temp-file + fsync + atomic-rename helper.

Every on-disk artifact the package folds across process lifetimes —
router calibration tables, cached substrate peaks, flight-recorder
dumps, graftwal checkpoints — used to hand-roll its own
write path, and most of them were plain ``open(path, "w")`` writes: a
crash (or ENOSPC) mid-write leaves truncated JSON that poisons every
future run that loads it.  The fix is the classic three-step dance, done
once, here:

1. write the full payload to a same-directory temp file (same filesystem,
   so the rename below is atomic);
2. ``flush`` + ``os.fsync`` the temp file so the *data* is on disk before
   the name is;
3. ``os.replace`` onto the destination — readers see the old complete
   file or the new complete file, never a prefix.

``fsync_dir=True`` additionally fsyncs the parent directory so the rename
itself survives power loss — graftwal checkpoints need that promise;
cache artifacts (recomputable) default to skipping it.

Deliberate leaf: stdlib only, importable from scripts/ and anywhere in
the package without cycles.
"""

from __future__ import annotations

import json
import os
from typing import Any


def fsync_dir(path: str) -> None:
    """fsync the directory containing ``path`` (rename durability)."""
    dirpath = os.path.dirname(os.path.abspath(path)) or "."
    fd = os.open(dirpath, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def atomic_write_bytes(
    path: str, data: bytes, durable_rename: bool = False
) -> None:
    """Write ``data`` to ``path`` atomically (temp + fsync + rename).

    On ANY failure the temp file is removed and the destination is
    untouched — a reader never observes a partial payload under ``path``.
    ``durable_rename=True`` also fsyncs the parent directory so the new
    name survives power loss (graftwal checkpoints); leave it off for
    recomputable cache artifacts.
    """
    path = os.fspath(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    if durable_rename:
        fsync_dir(path)


def atomic_write_text(
    path: str, text: str, durable_rename: bool = False
) -> None:
    """:func:`atomic_write_bytes` for UTF-8 text."""
    atomic_write_bytes(
        path, text.encode("utf-8"), durable_rename=durable_rename
    )


def atomic_write_json(
    path: str, obj: Any, durable_rename: bool = False, **dumps_kwargs: Any
) -> None:
    """:func:`atomic_write_bytes` for a JSON document (serialized FIRST,
    so a non-serializable object fails before any disk state changes)."""
    text = json.dumps(obj, **dumps_kwargs)
    atomic_write_bytes(
        path, text.encode("utf-8"), durable_rename=durable_rename
    )
