"""Partitioned SQL reader: LIMIT/OFFSET splitting + threaded fetch.

Reference design: modin/core/io/sql/sql_dispatcher.py:32 — the query is
wrapped in per-partition OFFSET/LIMIT subqueries, each fetched by its own
connection (``ModinDatabaseConnection`` makes the descriptor distributable),
then assembled into device columns.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import pandas

from modin_tpu.config import CpuCount, NPartitions
from modin_tpu.core.io.file_dispatcher import FileDispatcher
from modin_tpu.db_conn import ModinDatabaseConnection

_MIN_PARALLEL_ROWS = 100_000


class SQLDispatcher(FileDispatcher):
    @classmethod
    def _read(cls, sql: Any = None, con: Any = None, index_col: Any = None, **kwargs: Any):
        if kwargs.get("chunksize") is not None:
            # iterator semantics: hand back pandas' chunk iterator untouched
            conn = con.get_connection() if isinstance(con, ModinDatabaseConnection) else con
            return pandas.read_sql(sql, conn, index_col=index_col, **kwargs)
        if not isinstance(con, ModinDatabaseConnection) or index_col is not None:
            # plain connections aren't distributable descriptors; read serially
            if isinstance(con, ModinDatabaseConnection):
                conn = con.get_connection()
                try:
                    df = pandas.read_sql(sql, conn, index_col=index_col, **kwargs)
                finally:
                    try:
                        conn.close()
                    except Exception:  # graftlint: disable=EXC-HYGIENE -- DB driver surface (sqlalchemy/dbapi) has no stable exception classification
                        pass
            else:
                df = pandas.read_sql(sql, con, index_col=index_col, **kwargs)
            return cls.query_compiler_cls.from_pandas(df, cls.frame_cls)
        query = sql if isinstance(sql, str) else str(sql)
        if not query.lstrip().lower().startswith("select"):
            query = f"SELECT * FROM {query}"
        params = kwargs.get("params")
        conn = con.get_connection()
        try:
            row_count = pandas.read_sql(
                con.row_count_query(query), conn, params=params
            ).iloc[0, 0]
        finally:
            try:
                conn.close()
            except Exception:  # graftlint: disable=EXC-HYGIENE -- same driver surface; partition probing falls back to one query
                pass
        row_count = int(row_count)
        if row_count < _MIN_PARALLEL_ROWS or not con.supports_stable_offset_partitioning():
            conn = con.get_connection()
            try:
                df = pandas.read_sql(query, conn, **kwargs)
            finally:
                conn.close()
            return cls.query_compiler_cls.from_pandas(df, cls.frame_cls)

        n_parts = max(CpuCount.get(), 2)
        chunk = -(-row_count // n_parts)

        def fetch(offset: int) -> pandas.DataFrame:
            local = con.get_connection()
            try:
                return pandas.read_sql(
                    con.partition_query(query, chunk, offset), local, **kwargs
                )
            finally:
                try:
                    local.close()
                except Exception:  # graftlint: disable=EXC-HYGIENE -- same driver surface; a failed chunk fetch falls back to one query
                    pass

        offsets = list(range(0, row_count, chunk))
        with ThreadPoolExecutor(max_workers=min(len(offsets), CpuCount.get() * 2)) as pool:
            frames = list(pool.map(fetch, offsets))
        result = pandas.concat(frames, ignore_index=True)
        return cls.query_compiler_cls.from_pandas(result, cls.frame_cls)

    @classmethod
    def write(cls, qc: Any, name: str, con: Any, **kwargs: Any):
        from modin_tpu.utils import qc_to_pandas_for_write

        # Series-shaped compilers write with Series.to_sql column naming
        df = qc_to_pandas_for_write(qc)
        if isinstance(con, ModinDatabaseConnection):
            connection = con.get_connection()
            try:
                return df.to_sql(name, connection, **kwargs)
            finally:
                connection.close()
        return df.to_sql(name, con, **kwargs)
