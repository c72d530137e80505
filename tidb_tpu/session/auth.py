"""Users, passwords, and privileges — the in-memory grant-table cache.

The reference loads mysql.user / mysql.tables_priv into an in-memory
cache (privilege/privileges/cache.go:246) and checks every statement
against it (privilege/privileges/privileges.go:62). This module is that
cache for the single-process engine: users carry a mysql_native_password
stage-2 hash (SHA1(SHA1(password))), grants are (privilege, db, table)
triples at global (*.*), database (db.*), or table scope, and the session
checks the statement-kind → privilege mapping before executing.

`root` exists from bootstrap with an empty password and ALL PRIVILEGES —
the reference's bootstrap user (session/bootstrap.go)."""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Set, Tuple

from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.util import timeline

DEFAULT_DB = "test"      # the engine's single implicit database


class PrivilegeError(TiDBTPUError):
    code = 1142          # ER_TABLEACCESS_DENIED_ERROR


class AccessDeniedError(TiDBTPUError):
    code = 1045          # ER_ACCESS_DENIED_ERROR


PRIVS = {"SELECT", "INSERT", "UPDATE", "DELETE", "CREATE", "DROP",
         "ALTER", "INDEX", "PROCESS", "SUPER", "ALL"}


def stage2_of(password: str) -> bytes:
    if password == "":
        return b""
    return hashlib.sha1(hashlib.sha1(password.encode()).digest()).digest()


def _parse_scope(scope: str) -> Tuple[str, str]:
    """'*.*' | 'db.*' | 'db.tbl' | 'tbl' → (db, table), '*' = wildcard.
    A bare table name scopes to the default database."""
    scope = scope.lower()
    if "." in scope:
        db, tbl = scope.split(".", 1)
        return db, tbl
    return DEFAULT_DB, scope


class AuthManager:
    """Engine-wide user/grant registry (Domain-owned, like the reference's
    privilege Handle). All reads snapshot under the same lock the writers
    hold — sessions run on server threads concurrently."""

    def __init__(self):
        self._lock = timeline.named_lock("auth")
        self.users: Dict[str, bytes] = {"root": b""}
        # user → {(db, tbl) → privileges}
        self.grants: Dict[str, Dict[Tuple[str, str], Set[str]]] = {
            "root": {("*", "*"): {"ALL"}}}

    # -- persistence (mysql.user / mysql.global_variables analog) ------------
    def dump_state(self) -> dict:
        with self._lock:
            return {
                "users": {u: pw.hex() for u, pw in self.users.items()},
                "grants": {u: [[db, tbl, sorted(privs)]
                               for (db, tbl), privs in g.items()]
                           for u, g in self.grants.items()},
            }

    def load_state(self, state: dict) -> None:
        with self._lock:
            self.users = {u: bytes.fromhex(pw)
                          for u, pw in state.get("users", {}).items()}
            self.grants = {
                u: {(db, tbl): set(privs) for db, tbl, privs in g}
                for u, g in state.get("grants", {}).items()}

    # -- user admin ----------------------------------------------------------
    def create_user(self, user: str, password: str,
                    if_not_exists: bool = False) -> None:
        user = user.lower()
        with self._lock:
            if user in self.users:
                if if_not_exists:
                    return
                raise TiDBTPUError(f"Operation CREATE USER failed for "
                                   f"'{user}'@'%'")
            self.users[user] = stage2_of(password)
            self.grants.setdefault(user, {})

    def drop_user(self, user: str, if_exists: bool = False) -> None:
        user = user.lower()
        with self._lock:
            if user not in self.users:
                if if_exists:
                    return
                raise TiDBTPUError(f"Operation DROP USER failed for "
                                   f"'{user}'@'%'")
            del self.users[user]
            self.grants.pop(user, None)

    def set_password(self, user: str, password: str) -> None:
        user = user.lower()
        with self._lock:
            if user not in self.users:
                raise TiDBTPUError(f"Unknown user '{user}'")
            self.users[user] = stage2_of(password)

    def stage2(self, user: str) -> Optional[bytes]:
        with self._lock:
            return self.users.get(user.lower())

    # -- grants --------------------------------------------------------------
    def grant(self, user: str, privs: Set[str], scope: str) -> None:
        user = user.lower()
        with self._lock:
            if user not in self.users:
                raise TiDBTPUError(f"You are not allowed to create a user "
                                   f"with GRANT (unknown user '{user}')")
            bucket = self.grants.setdefault(user, {})
            bucket.setdefault(_parse_scope(scope), set()).update(
                p.upper() for p in privs)

    def revoke(self, user: str, privs: Set[str], scope: str) -> None:
        user = user.lower()
        with self._lock:
            bucket = self.grants.get(user, {})
            have = bucket.get(_parse_scope(scope))
            if have is None:
                raise TiDBTPUError(
                    "There is no such grant defined for user "
                    f"'{user}' on '{scope}'")
            have.difference_update(p.upper() for p in privs)
            if not have:
                del bucket[_parse_scope(scope)]

    def check(self, user: str, priv: str, table: Optional[str],
              db: str = DEFAULT_DB) -> bool:
        """priv on db.table; table None = a statement-level privilege,
        satisfied only by global or whole-database grants (never by a
        table-scoped grant — the escalation the reference's
        RequestVerification scoping prevents)."""
        priv = priv.upper()
        db = db.lower()
        with self._lock:
            bucket = {k: set(v) for k, v in
                      self.grants.get(user.lower(), {}).items()}
        for (sdb, stbl), privs in bucket.items():
            if "ALL" not in privs and priv not in privs:
                continue
            db_hit = sdb == "*" or sdb == db
            if not db_hit:
                continue
            if stbl == "*":
                return True
            if table is not None and stbl == table.lower():
                return True
        return False

    def is_superuser(self, user: str) -> bool:
        """ALL on *.* — required for user administration."""
        with self._lock:
            privs = self.grants.get(user.lower(), {}).get(("*", "*"))
        return bool(privs) and "ALL" in privs

    def has_global(self, user: str, priv: str) -> bool:
        """A global admin privilege (PROCESS, SUPER): satisfied ONLY by a
        *.* grant — MySQL refuses these at db/table scope, and a scoped
        grant must never escalate to seeing/killing other users' threads
        (mysql_acl's global_priv check)."""
        with self._lock:
            privs = self.grants.get(user.lower(), {}).get(("*", "*"),
                                                          set())
        return "ALL" in privs or priv.upper() in privs

    def require(self, user: str, priv: str, table: Optional[str],
                db: str = DEFAULT_DB) -> None:
        if not self.check(user, priv, table, db):
            tgt = f" on table '{table}'" if table else ""
            raise PrivilegeError(
                f"{priv} command denied to user '{user}'@'%'{tgt}")

    def show_grants(self, user: str) -> List[Tuple[str]]:
        user = user.lower()
        with self._lock:
            items = sorted(
                (f"{db}.{tbl}", sorted(privs))
                for (db, tbl), privs in self.grants.get(user, {}).items())
        out = []
        for scope, privs in items:
            plist = "ALL PRIVILEGES" if "ALL" in privs else ", ".join(privs)
            out.append((f"GRANT {plist} ON {scope} TO '{user}'@'%'",))
        if not out:
            out.append((f"GRANT USAGE ON *.* TO '{user}'@'%'",))
        return out
