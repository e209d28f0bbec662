"""graftfeed feeds: named continuous-ingestion targets with live views.

A :class:`Feed` owns one growing modin frame.  Micro-batches (pandas
frame / dict-of-columns / CSV text) are schema-validated (typed
:class:`~modin_tpu.ingest.errors.IngestRejected` on mismatch), then
appended through ``pd.concat`` — the ordinary graftplan path, so the
delta rides pushdown/pruning and graftview's ``concat_rows`` append
links keep ad-hoc queries on the frame folding.  Registered live views
(live.py) are maintained per batch: every fold leaves a per-batch
partial in the view log AND updates the running state, which is what
lets retention trims refold without touching row data.

Admission: appends and reads are both submitted through graftgate's ONE
admission gate (``serving.submit``) under the caller's tenant, so ingest
traffic bills against the same tenant buckets as queries.  Staleness:
``read(..., fresh_within_ms=...)`` serves the maintained artifact when
the fold lag (age of the oldest unfolded batch) is inside the bound and
forces a synchronous fold otherwise; every read feeds the per-view SLO
ring in graftwatch and the ``view.lag_ms`` histogram, and the watch
``fold_lag`` tripwire fires off :func:`max_fold_lag_ms`.

Locking: ``ingest.feeds`` guards the name table; each feed's ``ingest.
feed`` rlock serializes its frame/log/view state.  Metric fan-out always
runs after the locks release (the PR 9 gate-lock lesson).
"""

from __future__ import annotations

import io
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

import numpy as np

from modin_tpu.concurrency import named_lock, named_rlock
from modin_tpu.ingest.errors import IngestError, IngestRejected
from modin_tpu.ingest.live import LiveView, note_alloc
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability.spans import span

#: test seam for the smoke's injected slow-fold phase: seconds slept per
#: batch inside the fold loop (0.0 in production)
_FOLD_DELAY_S = 0.0


def _safely_castable(got: Any, want: Any) -> bool:
    """``np.can_cast(..., "safe")``, where a dtype numpy cannot interpret
    (a pandas extension dtype such as the pandas 3 string dtype) is simply
    not safely castable."""
    try:
        return bool(np.can_cast(got, want, casting="safe"))
    except TypeError:
        return False


class _BatchRecord:
    """One admitted micro-batch: its sequence number, row span, arrival
    stamps, and (until folded into every view) the host rows."""

    __slots__ = ("seq", "rows", "abs_start", "t_mono", "t_wall", "pdf")

    def __init__(self, seq: int, rows: int, abs_start: int, pdf: Any) -> None:
        note_alloc()
        self.seq = seq
        self.rows = rows
        self.abs_start = abs_start
        self.t_mono = time.monotonic()
        self.t_wall = time.time()
        self.pdf = pdf


class ViewRead:
    """One staleness-bounded read's answer + its freshness evidence."""

    __slots__ = (
        "value", "lag_ms", "forced", "covered_rows", "base_offset", "seq",
    )

    def __init__(self, value, lag_ms, forced, covered_rows, base_offset, seq):
        self.value = value
        self.lag_ms = lag_ms
        self.forced = forced
        self.covered_rows = covered_rows
        self.base_offset = base_offset
        self.seq = seq

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (
            f"<ViewRead lag={self.lag_ms:.1f}ms forced={self.forced} "
            f"covered={self.covered_rows}>"
        )


def _config():
    import modin_tpu.config as config

    return config


class Feed:
    """One named ingestion target.  Constructed via :func:`create_feed`."""

    def __init__(self, name: str, schema: Dict[str, Any],
                 key: Optional[str] = None,
                 retention_rows: Optional[int] = None,
                 retention_age_s: Optional[float] = None) -> None:
        import pandas

        import modin_tpu.pandas as mpd

        note_alloc()
        self.name = name
        self.schema: "OrderedDict[str, np.dtype]" = OrderedDict(
            (col, np.dtype(dt)) for col, dt in schema.items()
        )
        if key is not None and key not in self.schema:
            raise IngestError(
                f"feed {name!r}: key column {key!r} is not in the schema"
            )
        if retention_rows is not None and retention_rows < 0:
            raise IngestError(
                f"feed {name!r}: retention_rows must be >= 0, "
                f"got {retention_rows}"
            )
        if retention_age_s is not None and retention_age_s < 0:
            raise IngestError(
                f"feed {name!r}: retention_age_s must be >= 0, "
                f"got {retention_age_s}"
            )
        self.key = key
        #: per-feed retention overrides; None falls back to the
        #: MODIN_TPU_INGEST_RETENTION_ROWS / _AGE_S defaults at trim time
        self.retention_rows = retention_rows
        self.retention_age_s = retention_age_s
        self._lock = named_rlock("ingest.feed")
        self._mirror = pandas.DataFrame(
            {c: pandas.Series(dtype=d) for c, d in self.schema.items()}
        )
        self._frame = mpd.DataFrame(self._mirror)
        self._batches: "deque[_BatchRecord]" = deque()
        self._pending: "deque[_BatchRecord]" = deque()  # not yet folded
        self._views: Dict[str, LiveView] = {}
        self._key_index: Dict[Any, int] = {}  # key value -> retained position
        self._seq = -1
        self._rows = 0
        self._base_offset = 0  # absolute id of the first retained row
        #: graftwal manager when the feed was opened durable=True; None is
        #: the whole durability cost for ordinary feeds (the zero-overhead
        #: contract — one attribute check on the hot paths)
        self._wal = None

    # -- public surface (admitted through the serving gate) ------------ #

    @property
    def frame(self):
        """The feed's modin frame (ad-hoc queries fold via graftview)."""
        return self._frame

    @property
    def rows(self) -> int:
        return self._rows

    def append(self, batch: Any, tenant: str = "default"):
        """Admit one append micro-batch; returns the new retained row
        count.  Raises :class:`IngestRejected` on schema mismatch (and,
        on a keyed feed, when the batch repeats an existing key — that is
        :meth:`upsert`'s job)."""
        pdf = self._admit(batch)
        from modin_tpu import serving

        return serving.submit(
            self._append_sync, pdf, False,
            tenant=tenant, label=f"ingest.{self.name}",
        )

    def upsert(self, batch: Any, tenant: str = "default"):
        """Admit one upsert micro-batch (keyed feeds): rows whose key
        exists update in place (batch last-wins), the rest append."""
        if self.key is None:
            emit_metric("ingest.reject", 1)
            self._reject("not_keyed", detail="feed has no key column")
        pdf = self._admit(batch)
        from modin_tpu import serving

        return serving.submit(
            self._append_sync, pdf, True,
            tenant=tenant, label=f"ingest.{self.name}",
        )

    def register_view(self, name: str, plan: Dict[str, Any]) -> LiveView:
        """Register a named live view, maintained on every ingest from now
        on (existing retained rows fold in as the view's bootstrap
        partial).  Refuses non-incrementalizable plans with a typed
        :class:`ViewNotIncrementalizable` — never silently recomputed."""
        try:
            view = LiveView(self.name, name, plan, self.schema)
        except Exception:
            emit_metric("ingest.view.refused", 1)
            raise
        dur = self._wal
        # refused plans raise above, so a registration only reaches the
        # WAL once it validated; pickling happens here, outside the lock
        encoded = dur.encode_register(name, plan) if dur is not None else None
        dur_events = [] if dur is not None else None
        try:
            with self._lock:
                if name in self._views:
                    raise IngestError(
                        f"feed {self.name!r}: view {name!r} already registered"
                    )
                if encoded is not None:
                    # on disk BEFORE the view exists in memory
                    dur.log_encoded(encoded, dur_events)
                # graftlint: disable=LOCK-BLOCKING -- _FOLD_DELAY_S is a test-only fault hook (default 0.0); folding under the feed lock IS the contract: views advance atomically w.r.t. appends and trims
                self._fold_pending_locked()
                if self._rows:
                    view.rebuild(self._mirror, self._base_offset, self._seq)
                else:
                    view.folded_seq = self._seq
                self._views[name] = view
        finally:
            if dur is not None:
                dur.fanout(dur_events)
        return view

    def read(self, view_name: str, fresh_within_ms: Optional[float] = None,
             tenant: str = "default") -> ViewRead:
        """One staleness-bounded read, admitted under ``tenant``: serves
        the maintained state when fold lag <= ``fresh_within_ms``, else
        folds the pending batches synchronously first."""
        from modin_tpu import serving

        return serving.submit(
            self._read_sync, view_name, fresh_within_ms,
            tenant=tenant, label=f"ingest.read.{self.name}",
        )

    def fold_now(self) -> None:
        """Fold every pending batch (tests / draining)."""
        with self._lock:
            # graftlint: disable=LOCK-BLOCKING -- _FOLD_DELAY_S is a test-only fault hook (default 0.0); folding under the feed lock IS the contract: views advance atomically w.r.t. appends and trims
            folded = self._fold_pending_locked()
        if folded:
            emit_metric("ingest.fold", folded)

    def fold_lag_ms(self) -> float:
        with self._lock:
            return self._fold_lag_ms_locked()

    def views(self) -> List[str]:
        with self._lock:
            return sorted(self._views)

    def recompute(self, view_name: str) -> Any:
        """The view's answer recomputed FROM SCRATCH through the modin
        frame (the graftplan query path — no maintained state consulted):
        the differential baseline and the bench's recompute leg."""
        with self._lock:
            view = self._views.get(view_name)
            if view is None:
                raise IngestError(
                    f"feed {self.name!r} has no view {view_name!r}"
                )
            plan = view.plan
            kind = view.kind
            frame = self._frame
        col = plan.get("column")
        if kind == "scalar":
            return getattr(frame[col], plan["agg"])()
        if kind == "filtered":
            pcol, op, val = plan["predicate"]
            lhs = frame[pcol]
            mask = {
                ">": lhs > val, ">=": lhs >= val, "<": lhs < val,
                "<=": lhs <= val, "==": lhs == val, "!=": lhs != val,
            }[op]
            return getattr(frame[col][mask], plan["agg"])()
        if kind == "groupby":
            grouped = frame.groupby(plan["by"])[col]
            agg = plan["agg"]
            if agg == "size":
                result = frame.groupby(plan["by"]).size()
            else:
                result = getattr(grouped, agg)()
            return result._to_pandas() if hasattr(result, "_to_pandas") else result
        # topk / windowed recompute over the materialized frame
        pdf = frame._to_pandas().reset_index(drop=True)
        if kind == "topk":
            return pdf[col].nlargest(plan["k"], keep="first")
        import pandas

        ts = pdf[plan["time_column"]]
        keep = ts.notna()
        keys = np.floor(
            ts[keep].to_numpy(dtype=np.float64) / plan["bucket_s"]
        ).astype(np.int64)
        agg = plan["agg"]
        grouped = pdf[col][keep].groupby(keys)
        return getattr(grouped, agg)()

    # -- internals ----------------------------------------------------- #

    def _reject(self, reason: str, **kwargs) -> None:
        """Raise the typed rejection.  Raise-only on purpose: callers emit
        the ``ingest.reject`` counter AFTER any held locks release (the
        PR 9 gate-lock lesson — a slow metric handler must never stall
        appends/reads/trims holding the feed rlock)."""
        raise IngestRejected(self.name, reason, **kwargs)

    def _admit(self, batch: Any) -> Any:
        """Normalize an incoming batch outside any lock, counting
        rejections here where no lock is held."""
        try:
            return self._normalize(batch)
        except IngestRejected:
            emit_metric("ingest.reject", 1)
            raise

    def _normalize(self, batch: Any) -> Any:
        """Coerce an incoming batch (pandas / dict / CSV text) to a
        schema-exact pandas frame, or raise :class:`IngestRejected`."""
        import pandas

        if isinstance(batch, str):
            try:
                pdf = pandas.read_csv(io.StringIO(batch))
            except Exception as err:
                self._reject("malformed", detail=f"CSV parse failed: {err}")
        elif isinstance(batch, dict):
            try:
                pdf = pandas.DataFrame(batch)
            except Exception as err:
                self._reject("malformed", detail=str(err))
        elif isinstance(batch, pandas.DataFrame):
            pdf = batch.copy()
        elif hasattr(batch, "_to_pandas"):
            pdf = batch._to_pandas()
        else:
            self._reject(
                "unsupported_type", got=type(batch).__name__,
                expected="DataFrame | dict | CSV text",
            )
        got_cols = set(pdf.columns)
        for col in self.schema:
            if col not in got_cols:
                self._reject("missing_column", column=col)
        for col in pdf.columns:
            if col not in self.schema:
                self._reject("extra_column", column=str(col))
        pdf = pdf[list(self.schema)].reset_index(drop=True)
        for col, want in self.schema.items():
            got = pdf[col].dtype
            if got == want:
                continue
            if _safely_castable(got, want):
                pdf[col] = pdf[col].astype(want)
            else:
                self._reject(
                    "dtype", column=col, expected=str(want), got=str(got)
                )
        return pdf

    def _append_sync(self, pdf: Any, is_upsert: bool) -> int:
        dur = self._wal
        # serialize the batch for the WAL outside every lock (pickle is a
        # LOCK-BLOCKING operation); None = nothing to log (non-durable
        # feed, degraded breaker, or this call IS the replay)
        encoded = dur.encode_batch(pdf, is_upsert) if dur is not None else None
        dur_events = [] if dur is not None else None
        try:
            try:
                rows, upserted, appended, folded, trimmed = (
                    self._append_locked(pdf, is_upsert, encoded, dur_events)
                )
            except IngestRejected:
                # key-violation rejects raise under the feed rlock; the
                # counter fans out here, after it released
                emit_metric("ingest.reject", 1)
                raise
        finally:
            # wal.* events (including those of a refusing DurabilityError
            # path, e.g. an exhausted ENOSPC reclaim) fan out lock-free
            if dur is not None:
                dur.fanout(dur_events)
        if appended:
            emit_metric("ingest.batch", 1)
            emit_metric("ingest.rows", appended)
        if upserted:
            emit_metric("ingest.upsert", upserted)
        if folded:
            emit_metric("ingest.fold", folded)
        if trimmed:
            emit_metric("ingest.trim.rows", trimmed)
        if dur is not None:
            dur.maybe_checkpoint()
        return rows

    def _append_locked(self, pdf: Any, is_upsert: bool,
                       encoded=None, dur_events=None):
        import pandas

        import modin_tpu.pandas as mpd

        upserted = appended = folded = trimmed = 0
        with span("ingest.append", layer="APP", feed=self.name,
                  rows=len(pdf)):
            with self._lock:
                if not is_upsert and self.key is not None and len(pdf):
                    # key violations reject BEFORE the WAL sees the batch
                    # (rejects are never logged); moved ahead of the log
                    # call from the elif below for exactly that ordering
                    dup = pdf[self.key].duplicated(keep=False)
                    if bool(dup.any()):
                        self._reject(
                            "duplicate_key",
                            column=self.key,
                            detail="batch repeats a key; keys must be "
                            "unique within an append",
                        )
                    for k in pdf[self.key]:
                        if k in self._key_index:
                            self._reject(
                                "key_exists", column=self.key, got=k,
                                detail="append repeats a stored key — use "
                                "upsert",
                            )
                if encoded is not None and len(pdf):
                    # write-ahead: the record is on disk (per the fsync
                    # policy) before ANY in-memory mutation below; an
                    # exhausted-ENOSPC DurabilityError refuses the batch
                    # here with the feed state untouched
                    self._wal.log_encoded(encoded, dur_events)
                if is_upsert and len(pdf):
                    # batch last-wins among duplicate keys
                    pdf = pdf.drop_duplicates(
                        subset=[self.key], keep="last"
                    ).reset_index(drop=True)
                    hit = pdf[self.key].map(
                        lambda k: k in self._key_index
                    ).to_numpy(dtype=bool)
                    updates, pdf = pdf[hit], pdf[~hit].reset_index(drop=True)
                    if len(updates):
                        positions = [
                            self._key_index[k] for k in updates[self.key]
                        ]
                        for col in self.schema:
                            self._mirror.loc[
                                positions, col
                            ] = updates[col].to_numpy()
                        self._rebuild_frame_locked(mpd)
                        self._rebuild_views_locked()
                        upserted = len(updates)
                if len(pdf):
                    self._seq += 1
                    rec = _BatchRecord(
                        self._seq, len(pdf),
                        self._base_offset + self._rows, pdf,
                    )
                    if self.key is not None:
                        base = self._rows
                        for i, k in enumerate(pdf[self.key]):
                            self._key_index[k] = base + i
                    self._mirror = pandas.concat(
                        [self._mirror, pdf], ignore_index=True
                    )
                    self._frame = mpd.concat(
                        [self._frame, mpd.DataFrame(pdf)], ignore_index=True
                    )
                    self._rows += len(pdf)
                    self._batches.append(rec)
                    self._pending.append(rec)
                    appended = len(pdf)
                    every = int(_config().IngestFoldEvery.get())
                    if every <= 1 or (self._seq + 1) % every == 0:
                        # graftlint: disable=LOCK-BLOCKING -- _FOLD_DELAY_S is a test-only fault hook (default 0.0); folding under the feed lock IS the contract: views advance atomically w.r.t. appends and trims
                        folded = self._fold_pending_locked()
                trimmed = self._trim_locked()
                rows = self._rows
        return rows, upserted, appended, folded, trimmed

    def _rebuild_frame_locked(self, mpd) -> None:
        self._frame = mpd.DataFrame(self._mirror)

    def _rebuild_views_locked(self) -> None:
        """Collapse every view to a bootstrap partial over the retained
        frame (upsert / bootstrap-intersecting trim): the exact-rebuild
        path.  Pending batches are covered by the rebuild, so they drain."""
        self._pending.clear()
        for rec in self._batches:
            rec.pdf = None
        rebuilt = 0
        for view in self._views.values():
            view.rebuild(self._mirror, self._base_offset, self._seq)
            rebuilt += 1
        if rebuilt:
            emit_metric("ingest.rebuild", rebuilt)

    def _fold_pending_locked(self) -> int:
        folded = 0
        while self._pending:
            rec = self._pending.popleft()
            with span("ingest.fold", layer="APP", feed=self.name,
                      seq=rec.seq):
                if _FOLD_DELAY_S > 0.0:
                    time.sleep(_FOLD_DELAY_S)
                for view in self._views.values():
                    view.fold_batch(rec.seq, rec.pdf, rec.abs_start)
            rec.pdf = None
            folded += 1
        return folded

    def _fold_lag_ms_locked(self) -> float:
        if not self._pending:
            return 0.0
        return (time.monotonic() - self._pending[0].t_mono) * 1e3

    def _trim_locked(self) -> int:
        """Retention: drop oldest whole batches past the row-count / age
        bounds.  Views refold from their retained per-batch partials —
        host-side combines only, no recompute (unless the trim reaches
        into a view's bootstrap span, which forces its exact rebuild)."""
        config = _config()
        max_rows = (
            int(self.retention_rows) if self.retention_rows is not None
            else int(config.IngestRetentionRows.get())
        )
        max_age = (
            float(self.retention_age_s) if self.retention_age_s is not None
            else float(config.IngestRetentionAgeS.get())
        )
        now = time.monotonic()
        dropped: List[_BatchRecord] = []
        remaining = self._rows
        while len(self._batches) > 1 and (
            (max_rows > 0 and remaining > max_rows)
            or (max_age > 0.0 and now - self._batches[0].t_mono > max_age)
        ):
            rec = self._batches.popleft()
            remaining -= rec.rows
            dropped.append(rec)
        if not dropped:
            return 0
        import modin_tpu.pandas as mpd

        trimmed_rows = sum(rec.rows for rec in dropped)
        dropped_seqs = [rec.seq for rec in dropped]
        pending_dropped = {rec.seq for rec in dropped}
        self._pending = deque(
            rec for rec in self._pending if rec.seq not in pending_dropped
        )
        self._mirror = self._mirror.iloc[trimmed_rows:].reset_index(drop=True)
        self._rows -= trimmed_rows
        self._base_offset += trimmed_rows
        self._rebuild_frame_locked(mpd)
        if self.key is not None:
            self._key_index = {
                k: pos for k, pos in (
                    (row[self.key], i)
                    for i, row in enumerate(
                        self._mirror.to_dict(orient="records")
                    )
                )
            }
        needs_rebuild = False
        for view in self._views.values():
            if view.drop_batches(dropped_seqs):
                needs_rebuild = True
        if needs_rebuild:
            self._rebuild_views_locked()
        return trimmed_rows

    def _read_sync(self, view_name: str,
                   fresh_within_ms: Optional[float]) -> ViewRead:
        forced = False
        with span("ingest.read", layer="APP", feed=self.name,
                  view=view_name):
            with self._lock:
                view = self._views.get(view_name)
                if view is None:
                    raise IngestError(
                        f"feed {self.name!r} has no view {view_name!r}"
                    )
                lag = self._fold_lag_ms_locked()
                if fresh_within_ms is not None and lag > fresh_within_ms:
                    forced = True
                    # graftlint: disable=LOCK-BLOCKING -- _FOLD_DELAY_S is a test-only fault hook (default 0.0); folding under the feed lock IS the contract: views advance atomically w.r.t. appends and trims
                    self._fold_pending_locked()
                    lag = 0.0
                value = view.value(self._base_offset)
                pending_rows = sum(rec.rows for rec in self._pending)
                covered = self._rows - pending_rows
                result = ViewRead(
                    value, lag, forced, covered, self._base_offset,
                    view.folded_seq,
                )
        if forced:
            emit_metric("ingest.read.forced_fold", 1)
        else:
            emit_metric("ingest.read.served", 1)
        emit_metric("view.lag_ms", lag)
        from modin_tpu.observability import watch as _watch

        if _watch.WATCH_ON:
            _watch.observe_view_read(
                f"{self.name}/{view_name}", lag / 1e3
            )
        return result


# --------------------------------------------------------------------- #
# the feeds table
# --------------------------------------------------------------------- #

_FEEDS_LOCK = named_lock("ingest.feeds")
_feeds: Dict[str, Feed] = {}


def create_feed(name: str, schema: Dict[str, Any],
                key: Optional[str] = None,
                retention_rows: Optional[int] = None,
                retention_age_s: Optional[float] = None) -> Feed:
    """Create and register a named feed.  Requires ``MODIN_TPU_INGEST=1``
    (the subsystem is off by default — the zero-overhead contract).
    ``retention_rows`` / ``retention_age_s`` override the
    ``MODIN_TPU_INGEST_RETENTION_ROWS`` / ``_AGE_S`` defaults for this
    feed (0 = unbounded, None = inherit the knob)."""
    from modin_tpu import ingest as _ingest

    if not _ingest.INGEST_ON:
        raise IngestError(
            "continuous ingestion is disabled; set MODIN_TPU_INGEST=1 "
            "(config.IngestEnabled.enable())"
        )
    feed = Feed(name, schema, key=key, retention_rows=retention_rows,
                retention_age_s=retention_age_s)
    with _FEEDS_LOCK:
        if name in _feeds:
            raise IngestError(f"feed {name!r} already exists")
        _feeds[name] = feed
    return feed


def open_feed(name: str, schema: Optional[Dict[str, Any]] = None,
              key: Optional[str] = None,
              retention_rows: Optional[int] = None,
              retention_age_s: Optional[float] = None,
              durable: bool = False,
              durability_dir: Optional[str] = None) -> Feed:
    """:func:`create_feed`, plus the graftwal door.  ``durable=False``
    (the default) is exactly ``create_feed`` — the durability package is
    not even imported, so ordinary feeds stay bit-for-bit unchanged.

    ``durable=True`` lazy-imports ``modin_tpu.durability`` and opens a
    write-ahead-logged feed under ``durability_dir`` (default:
    ``MODIN_TPU_WAL_DIR``, else ``<MODIN_TPU_CACHE_DIR>/wal``).  A fresh
    feed needs a ``schema``; an existing durability directory is
    RECOVERED — newest valid checkpoint plus WAL-tail replay through the
    ordinary ingest path, run under the serving gate as a maintenance
    query — and ``schema`` may then be omitted (it is read from the
    feed's ``meta.json``; supplying a contradicting one is a typed
    ``DurabilityError``)."""
    if not durable:
        if schema is None:
            raise IngestError(
                f"feed {name!r}: a non-durable open_feed needs a schema"
            )
        return create_feed(name, schema, key=key,
                           retention_rows=retention_rows,
                           retention_age_s=retention_age_s)
    from modin_tpu import ingest as _ingest

    if not _ingest.INGEST_ON:
        raise IngestError(
            "continuous ingestion is disabled; set MODIN_TPU_INGEST=1 "
            "(config.IngestEnabled.enable())"
        )
    from modin_tpu import durability as _durability

    feed = _durability.open_durable_feed(
        name, schema, key=key, retention_rows=retention_rows,
        retention_age_s=retention_age_s, root_dir=durability_dir,
    )
    with _FEEDS_LOCK:
        conflict = name in _feeds
        if not conflict:
            _feeds[name] = feed
    if conflict:
        feed._wal.close()  # outside the table lock (join is blocking)
        raise IngestError(f"feed {name!r} already exists")
    return feed


def get_feed(name: str) -> Feed:
    with _FEEDS_LOCK:
        feed = _feeds.get(name)
    if feed is None:
        raise IngestError(f"no feed named {name!r}")
    return feed


def drop_feed(name: str) -> None:
    with _FEEDS_LOCK:
        feed = _feeds.pop(name, None)
    if feed is not None and feed._wal is not None:
        # final fsync + flusher join happen OUTSIDE the table lock
        feed._wal.close()


def feeds() -> List[str]:
    with _FEEDS_LOCK:
        return sorted(_feeds)


def max_fold_lag_ms() -> float:
    """The worst fold lag across every live feed — what the graftwatch
    ``fold_lag`` tripwire evaluates each sampler tick."""
    with _FEEDS_LOCK:
        snapshot = list(_feeds.values())
    lag = 0.0
    for feed in snapshot:
        lag = max(lag, feed.fold_lag_ms())
    return lag


def reset() -> None:
    """Drop every feed (tests)."""
    with _FEEDS_LOCK:
        snapshot = list(_feeds.values())
        _feeds.clear()
    for feed in snapshot:
        if feed._wal is not None:
            feed._wal.close()
