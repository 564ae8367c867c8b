"""Accounts, users, roles, privileges, tenant scoping.

Reference analogue: `pkg/frontend/authenticate.go` + the mo_account /
mo_user / mo_role / mo_role_privs system tables — MatrixOne logs in as
`account:user`, resolves privileges through roles, and scopes every
catalog object to the account (tenant).

Redesign here:
  * auth state lives in ordinary engine tables (mo_account, mo_user,
    mo_role, mo_user_role, mo_priv) — so it WAL-logs, checkpoints, and
    replicates to every CN through the logtail like any other data (the
    reference stores them in mo_catalog for the same reason);
  * an in-memory mirror rebuilds lazily and is invalidated by the
    engine's logtail subscriber hook, so per-statement privilege checks
    never rescan tables;
  * tenant scoping is a catalog wrapper (`ScopedCatalog`) that maps
    `name` -> `account$name` at the engine boundary — one shared
    catalog, per-account namespaces, exactly the reference's account_id
    scoping expressed as a prefix.
"""

from __future__ import annotations

import dataclasses
import threading

from matrixone_tpu.utils import san
from typing import Dict, List, Optional, Set

import numpy as np

from matrixone_tpu.container.dtypes import VARCHAR
from matrixone_tpu.storage.engine import TableMeta, live_rows

SYS_ACCOUNT = "sys"
ADMIN_ROLE = "accountadmin"
PRIVS = frozenset(["select", "insert", "update", "delete", "create",
                   "drop", "all"])

_AUTH_TABLES = {
    "mo_account": [("name", VARCHAR), ("admin_user", VARCHAR)],
    "mo_user": [("account", VARCHAR), ("name", VARCHAR),
                ("stage2", VARCHAR)],
    "mo_role": [("account", VARCHAR), ("name", VARCHAR)],
    "mo_user_role": [("account", VARCHAR), ("user", VARCHAR),
                     ("role", VARCHAR)],
    "mo_priv": [("account", VARCHAR), ("role", VARCHAR),
                ("obj", VARCHAR), ("priv", VARCHAR)],
}


class AuthError(RuntimeError):
    pass


@dataclasses.dataclass
class AuthContext:
    account: str
    user: str
    is_admin: bool           # account admin (or sys root): full account


def _stage2_hex(password: str) -> str:
    from matrixone_tpu.frontend.server import password_stage2
    return password_stage2(password).hex() if password else ""


class AccountManager:
    """Durable account/user/role/privilege state + cached mirror."""

    def __init__(self, engine,
                 seed_users: Optional[Dict[str, bytes]] = None):
        """`seed_users` maps sys-account usernames to stage2 hashes (the
        MOServer `users` config); 'root' defaults to an empty password."""
        self.engine = engine
        self._lock = san.lock("AccountManager._lock")
        self._mirror = None
        self._gen = 0          # bumped on every auth-table change
        self._bootstrap(dict(seed_users or {}))
        engine.subscribe(self._on_change)

    # ------------------------------------------------------- bootstrap
    def _bootstrap(self, seed: Dict[str, bytes]):
        eng = self.engine
        for name, schema in _AUTH_TABLES.items():
            if name not in eng.tables:
                eng.create_table(TableMeta(name, list(schema), []),
                                 if_not_exists=True)
        if not self._rows("mo_account"):
            # the sys account's admin is the first seeded user; a config
            # that omits 'root' gets NO root login (no silent
            # passwordless backdoor)
            admin = "root" if "root" in seed or not seed \
                else next(iter(seed))
            self._insert("mo_account", {"name": SYS_ACCOUNT,
                                        "admin_user": admin})
            self._insert("mo_role", {"account": SYS_ACCOUNT,
                                     "name": ADMIN_ROLE})
            self._insert("mo_priv", {"account": SYS_ACCOUNT,
                                     "role": ADMIN_ROLE, "obj": "*",
                                     "priv": "all"})
            if not seed:
                seed = {"root": b""}     # default config: root, empty pw
        for user, stage2 in seed.items():
            row = self._user_row(SYS_ACCOUNT, user)
            if row is None:
                self._insert("mo_user", {"account": SYS_ACCOUNT,
                                         "name": user,
                                         "stage2": stage2.hex()})
                self._insert("mo_user_role", {"account": SYS_ACCOUNT,
                                              "user": user,
                                              "role": ADMIN_ROLE})
            elif row["stage2"] != stage2.hex():
                # restart with a changed configured password: the config
                # wins (replace the stored hash)
                self._delete("mo_user", {"account": SYS_ACCOUNT,
                                         "name": user})
                self._insert("mo_user", {"account": SYS_ACCOUNT,
                                         "name": user,
                                         "stage2": stage2.hex()})

    # ------------------------------------------------------- table io
    def _rows(self, table: str) -> List[dict]:
        t = self.engine.get_table(table)
        cols = [c for c, _ in t.meta.schema]
        out: List[dict] = []
        for arrays, validity, dicts, n in map(live_rows, t.iter_chunks(
                cols + ["__rowid"], 1 << 20)):
            decoded = {}
            for c in cols:
                d = dicts.get(c, [])
                decoded[c] = [d[int(v)] if ok and 0 <= int(v) < len(d)
                              else None
                              for v, ok in zip(np.asarray(arrays[c]),
                                               validity[c])]
            gids = np.asarray(arrays["__rowid"])
            for i in range(n):
                row = {c: decoded[c][i] for c in cols}
                row["__gid"] = int(gids[i])
                out.append(row)
        return out

    def _insert(self, table: str, row: Dict[str, str]) -> None:
        t = self.engine.get_table(table)
        strings = {c: (np.zeros(1, np.int32), [v if v is not None else ""])
                   for c, v in row.items()}
        t.insert_numpy({}, strings=strings)
        # own writes invalidate directly: the engine subscriber hook only
        # registers after bootstrap, and a cached pre-write mirror must
        # never survive the write that outdated it
        self._mirror = None
        self._gen += 1

    def _delete(self, table: str, match: Dict[str, str]) -> int:
        gids = [r["__gid"] for r in self._rows(table)
                if all(r.get(k) == v for k, v in match.items())]
        if gids:
            self.engine.commit_txn(None, {}, {
                table: np.asarray(gids, np.int64)})
            self._mirror = None
            self._gen += 1
        return len(gids)

    # --------------------------------------------------------- mirror
    def _on_change(self, ts, table, kind, payload) -> None:
        if table in _AUTH_TABLES:
            self._mirror = None
            self._gen += 1

    def _m(self) -> dict:
        m = self._mirror
        if m is not None:
            return m
        with self._lock:
            if self._mirror is not None:
                return self._mirror
            while True:
                m = self._build_mirror()
                # a write that landed mid-rebuild already invalidated the
                # cache; installing the stale snapshot would honor
                # revoked privileges until the NEXT change — rebuild
                if self._gen == m["_gen"]:
                    self._mirror = m
                    return m

    def _build_mirror(self) -> dict:
        gen = self._gen
        m = {
            "accounts": {r["name"]: r for r in self._rows("mo_account")},
            "users": {(r["account"], r["name"]): r
                      for r in self._rows("mo_user")},
            "roles": {(r["account"], r["name"]) for r
                      in self._rows("mo_role")},
            "user_roles": {},
            "privs": {},
        }
        for r in self._rows("mo_user_role"):
            m["user_roles"].setdefault(
                (r["account"], r["user"]), set()).add(r["role"])
        for r in self._rows("mo_priv"):
            m["privs"].setdefault(
                (r["account"], r["role"]), []).append(
                    (r["obj"], r["priv"]))
        m["_gen"] = gen
        return m

    # ----------------------------------------------------------- login
    def resolve_login(self, username: str):
        """'account:user' (or plain 'user' = sys) -> (account, user,
        stage2 bytes) or None."""
        if ":" in username:
            account, user = username.split(":", 1)
        else:
            account, user = SYS_ACCOUNT, username
        row = self._m()["users"].get((account, user))
        if row is None:
            return None
        stage2 = bytes.fromhex(row["stage2"]) if row["stage2"] else b""
        return account, user, stage2

    def context_for(self, account: str, user: str) -> AuthContext:
        m = self._m()
        acct = m["accounts"].get(account)
        is_admin = bool(acct and acct["admin_user"] == user) or \
            ADMIN_ROLE in m["user_roles"].get((account, user), set())
        return AuthContext(account=account, user=user, is_admin=is_admin)

    def _user_row(self, account: str, user: str):
        return self._m()["users"].get((account, user))

    # ------------------------------------------------------ management
    def create_account(self, name: str, admin_user: str,
                       admin_password: str,
                       if_not_exists: bool = False) -> None:
        if name in self._m()["accounts"]:
            if if_not_exists:
                return
            raise AuthError(f"account {name!r} already exists")
        if "$" in name or ":" in name:
            raise AuthError("account names may not contain '$' or ':'")
        self._insert("mo_account", {"name": name,
                                    "admin_user": admin_user})
        self._insert("mo_user", {"account": name, "name": admin_user,
                                 "stage2": _stage2_hex(admin_password)})
        self._insert("mo_role", {"account": name, "name": ADMIN_ROLE})
        self._insert("mo_user_role", {"account": name, "user": admin_user,
                                      "role": ADMIN_ROLE})
        self._insert("mo_priv", {"account": name, "role": ADMIN_ROLE,
                                 "obj": "*", "priv": "all"})

    def drop_account(self, name: str) -> None:
        if name == SYS_ACCOUNT:
            raise AuthError("cannot drop the sys account")
        if name not in self._m()["accounts"]:
            raise AuthError(f"no such account {name!r}")
        for table in ("mo_priv", "mo_user_role", "mo_role", "mo_user",
                      "mo_account"):
            self._delete(table, {"account": name} if table != "mo_account"
                         else {"name": name})
        # the tenant's tables go with it
        prefix = f"{name}$"
        for tname in [t for t in self.engine.tables if
                      t.startswith(prefix)]:
            self.engine.drop_table(tname, if_exists=True)

    def create_user(self, account: str, name: str, password: str,
                    if_not_exists: bool = False) -> None:
        if self._user_row(account, name):
            if if_not_exists:
                return
            raise AuthError(f"user {name!r} already exists")
        self._insert("mo_user", {"account": account, "name": name,
                                 "stage2": _stage2_hex(password)})

    def drop_user(self, account: str, name: str) -> None:
        acct = self._m()["accounts"].get(account)
        if acct and acct["admin_user"] == name:
            raise AuthError("cannot drop the account admin")
        if not self._delete("mo_user", {"account": account, "name": name}):
            raise AuthError(f"no such user {name!r}")
        self._delete("mo_user_role", {"account": account, "user": name})

    def create_role(self, account: str, name: str) -> None:
        if (account, name) in self._m()["roles"]:
            raise AuthError(f"role {name!r} already exists")
        self._insert("mo_role", {"account": account, "name": name})

    def drop_role(self, account: str, name: str) -> None:
        if name == ADMIN_ROLE:
            raise AuthError("cannot drop the admin role")
        if not self._delete("mo_role", {"account": account, "name": name}):
            raise AuthError(f"no such role {name!r}")
        self._delete("mo_user_role", {"account": account, "role": name})
        self._delete("mo_priv", {"account": account, "role": name})

    def grant_priv(self, account: str, privs: List[str], obj: str,
                   role: str) -> None:
        if (account, role) not in self._m()["roles"]:
            raise AuthError(f"no such role {role!r}")
        for p in privs:
            if p not in PRIVS:
                raise AuthError(f"unknown privilege {p!r}")
            self._insert("mo_priv", {"account": account, "role": role,
                                     "obj": obj, "priv": p})

    def revoke_priv(self, account: str, privs: List[str], obj: str,
                    role: str) -> None:
        for p in privs:
            self._delete("mo_priv", {"account": account, "role": role,
                                     "obj": obj, "priv": p})

    def grant_role(self, account: str, role: str, user: str) -> None:
        if (account, role) not in self._m()["roles"]:
            raise AuthError(f"no such role {role!r}")
        if not self._user_row(account, user):
            raise AuthError(f"no such user {user!r}")
        self._insert("mo_user_role", {"account": account, "user": user,
                                      "role": role})

    def revoke_role(self, account: str, role: str, user: str) -> None:
        self._delete("mo_user_role", {"account": account, "user": user,
                                      "role": role})

    def grants_for(self, account: str, user: str) -> List[tuple]:
        m = self._m()
        out = []
        for role in sorted(m["user_roles"].get((account, user), set())):
            for obj, priv in m["privs"].get((account, role), []):
                out.append((role, obj, priv))
        return out

    # ----------------------------------------------------------- check
    def check(self, ctx: AuthContext, priv: str, obj: str = "*") -> None:
        """Raise AuthError unless ctx may exercise `priv` on `obj`
        (a table name, or '*' for account-level rights)."""
        if ctx.is_admin:
            return
        m = self._m()
        for role in m["user_roles"].get((ctx.account, ctx.user), set()):
            for gobj, gpriv in m["privs"].get((ctx.account, role), []):
                if gobj not in ("*", obj):
                    continue
                if gpriv == "all" or gpriv == priv:
                    return
        raise AuthError(
            f"access denied: user {ctx.user!r} of account "
            f"{ctx.account!r} lacks {priv.upper()} on {obj!r}")


class ScopedCatalog:
    """The engine surface a tenant session sees: every object name maps
    to `account$name` at this boundary, so one shared catalog carries
    per-account namespaces (the reference's account_id scoping)."""

    def __init__(self, inner, account: str):
        object.__setattr__(self, "_inner", inner)
        object.__setattr__(self, "_acct", account)
        object.__setattr__(self, "_prefix", f"{account}$")
        from matrixone_tpu.queryservice import registry_for
        registry_for(inner)          # share one processlist with root

    def _scope(self, name: str) -> str:
        return name if name.startswith(self._prefix) \
            else self._prefix + name

    def _unscope(self, name: str) -> str:
        return name[len(self._prefix):] \
            if name.startswith(self._prefix) else name

    def __getattr__(self, k):
        return getattr(object.__getattribute__(self, "_inner"), k)

    def __setattr__(self, k, v):
        setattr(object.__getattribute__(self, "_inner"), k, v)

    # ----------------------------------------------------- table reads
    @property
    def tables(self):
        return {self._unscope(k): v
                for k, v in self._inner.tables.items()
                if k.startswith(self._prefix)}

    def get_table(self, name: str):
        return self._inner.get_table(self._scope(name))

    def get_table_meta(self, name: str):
        return self._inner.get_table_meta(self._scope(name))

    # ----------------------------------------------------- table writes
    def _scoped_meta(self, meta: TableMeta) -> TableMeta:
        return dataclasses.replace(meta, name=self._scope(meta.name))

    def create_table(self, meta, **kw):
        return self._inner.create_table(self._scoped_meta(meta), **kw)

    def drop_table(self, name, *a, **kw):
        return self._inner.drop_table(self._scope(name), *a, **kw)

    def create_external(self, meta, *a, **kw):
        return self._inner.create_external(self._scoped_meta(meta),
                                           *a, **kw)

    def commit_write(self, table, arrays, validity):
        return self._inner.commit_write(self._scope(table), arrays,
                                        validity)

    def commit_txn(self, snapshot_ts, inserts, deletes):
        return self._inner.commit_txn(
            snapshot_ts,
            {self._scope(t): v for t, v in inserts.items()},
            {self._scope(t): v for t, v in deletes.items()})

    def merge_table(self, name, *a, **kw):
        return self._inner.merge_table(self._scope(name), *a, **kw)

    def restore_table(self, table, ts):
        return self._inner.restore_table(self._scope(table), ts)

    def register_dynamic(self, name, sql, **kw):
        return self._inner.register_dynamic(self._scope(name), sql, **kw)

    def mark_source(self, name, **kw):
        return self._inner.mark_source(self._scope(name), **kw)

    # -------------------------------------------------------- indexes
    # index metas keep their SCOPED names internally (plans carry them
    # through to the runtime lookups on the raw dict)
    def register_index(self, meta) -> None:
        meta.name = self._scope(meta.name)
        meta.table = self._scope(meta.table)
        self._inner.register_index(meta)

    def indexes_on(self, table: str):
        return self._inner.indexes_on(self._scope(table))
