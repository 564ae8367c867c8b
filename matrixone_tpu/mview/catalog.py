"""Materialized-view catalog: the `system_mview` table and the registry
derived from it.

Same design as udf/catalog.py (the reference's mo_user_defined_function
pattern applied to views): definitions live in an ordinary MVCC table so
durability, restart replay, tenant scoping (ScopedCatalog prefixes the
name) and CN replication (logtail insert/delete records) all ride the
funnels that already exist.  The in-memory registry is a cache DERIVED
from the table, keyed by the table's version — any commit, local or
logtail-applied, invalidates it.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional

import numpy as np

from matrixone_tpu.container import dtypes as dt

MVIEW_TABLE = "system_mview"

_SCHEMA = [
    ("name", dt.varchar(128)),
    ("sql", dt.TEXT),                  # the defining SELECT, verbatim
    ("mode", dt.varchar(16)),          # 'incremental' | 'full'
    ("source", dt.varchar(128)),       # single-table source ('' for full)
    ("created_ts", dt.INT64),
]


@dataclasses.dataclass
class MViewDef:
    name: str
    sql: str
    mode: str                          # 'incremental' | 'full'
    source: str                        # source table name ('' when full)
    created_ts: int = 0

    @property
    def def_hash(self) -> str:
        """Content key of the definition — the delta compile cache and
        runtime state key on it so OR-REPLACE-style churn (drop +
        recreate under the same name) can never serve stale programs."""
        return hashlib.sha1(
            f"{self.name}|{self.mode}|{self.sql}".encode()).hexdigest()


def table_meta():
    from matrixone_tpu.storage.engine import TableMeta
    return TableMeta(MVIEW_TABLE, list(_SCHEMA), ["name"])


def ensure_table(catalog) -> None:
    if MVIEW_TABLE not in catalog.tables:
        catalog.create_table(table_meta(), if_not_exists=True)


def is_mview_table(name: str) -> bool:
    """True for the sys table and every tenant-scoped `acct$system_mview`
    variant (the commit funnel uses this to bump ddl_gen)."""
    return name == MVIEW_TABLE or name.endswith("$" + MVIEW_TABLE)


# ------------------------------------------------------------- registry

def _table_version(t) -> tuple:
    return (t.last_commit_ts, len(t.segments), len(t.tombstones))


def _scan_rows(t) -> List[dict]:
    from matrixone_tpu.storage.engine import live_rows
    cols = [c for c, _ in _SCHEMA]
    rows: List[dict] = []
    for arrays, validity, dicts, n in map(
            live_rows, t.iter_chunks(cols, 1 << 16)):
        for i in range(n):
            row = {}
            for c, d in _SCHEMA:
                if not validity[c][i]:
                    row[c] = None
                elif d.is_varlen:
                    row[c] = dicts[c][int(arrays[c][i])]
                else:
                    row[c] = int(arrays[c][i])
            rows.append(row)
    return rows


def _has_mview_table(catalog) -> bool:
    scope = getattr(catalog, "_scope", None)
    if scope is not None:
        inner = getattr(catalog, "_inner", None)
        if inner is not None:
            return scope(MVIEW_TABLE) in inner.tables
    tables = getattr(catalog, "tables", None)
    return tables is not None and MVIEW_TABLE in tables


def registry_for(catalog) -> Dict[str, MViewDef]:
    """name -> MViewDef for every view visible through `catalog`.
    Cached on the underlying table object, invalidated by version."""
    if not _has_mview_table(catalog):
        return {}
    t = catalog.get_table(MVIEW_TABLE)
    t = getattr(t, "_t", t)          # unwrap the CN _TableProxy
    version = _table_version(t)
    cached = getattr(t, "_mview_registry", None)
    if cached is not None and cached[0] == version:
        return cached[1]
    reg: Dict[str, MViewDef] = {}
    for row in _scan_rows(t):
        try:
            d = MViewDef(name=row["name"], sql=row["sql"] or "",
                         mode=row["mode"] or "full",
                         source=row["source"] or "",
                         created_ts=row["created_ts"] or 0)
        except (KeyError, TypeError):
            continue              # malformed row: never poison binds
        reg[d.name.lower()] = d
    t._mview_registry = (version, reg)
    return reg


def lookup(catalog, name: str) -> Optional[MViewDef]:
    return registry_for(catalog).get(name.lower())


def gids_for_name(catalog, name: str) -> np.ndarray:
    """Global row ids of the view's catalog row(s) (DROP path)."""
    from matrixone_tpu.storage.engine import ROWID, live_rows
    t = catalog.get_table(MVIEW_TABLE)
    out = []
    for arrays, validity, dicts, n in map(
            live_rows, t.iter_chunks([ROWID, "name"], 1 << 16)):
        d = dicts["name"]
        for i in range(n):
            if validity["name"][i] and \
                    d[int(arrays["name"][i])].lower() == name.lower():
                out.append(int(arrays[ROWID][i]))
    return np.asarray(out, np.int64)


def row_batch(d: MViewDef, created_ts: int):
    """One-row host Batch for the insert side of CREATE MATERIALIZED
    VIEW."""
    from matrixone_tpu.container.batch import Batch
    vals = {"name": [d.name.lower()], "sql": [d.sql], "mode": [d.mode],
            "source": [d.source], "created_ts": [int(created_ts)]}
    return Batch.from_pydict(vals, dict(_SCHEMA))
