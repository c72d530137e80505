"""Minimal MySQL-protocol client (text path).

The reference ships tools (dumpling, br) that reach the cluster through
stock MySQL drivers; no driver ships in this image, so this is the
in-repo equivalent — handshake with mysql_native_password, COM_QUERY,
text resultset decoding. Used by tidb_tpu.tools (dump/CSV CLIs) and
available as a programmatic driver for the wire server.

Resilience: with auto_reconnect (default on), a connection the server
closed (KILL <id>, restart) is re-established with exponential backoff
and the statement retried — but ONLY for read-only statements, where the
retry cannot double-apply work (go-sql-driver's ErrBadConn contract:
never auto-retry a write on an ambiguous connection death)."""

from __future__ import annotations

import hashlib
import socket
import struct
import time
from typing import List, Optional, Tuple


class ClientError(RuntimeError):
    def __init__(self, code: int, msg: str):
        super().__init__(f"ERROR {code}: {msg}")
        self.code = code


def _scramble(password: str, salt: bytes) -> bytes:
    if not password:
        return b""
    sha_pw = hashlib.sha1(password.encode()).digest()
    stage2 = hashlib.sha1(sha_pw).digest()
    mix = hashlib.sha1(salt + stage2).digest()
    return bytes(a ^ b for a, b in zip(sha_pw, mix))


# statements safe to replay on a fresh connection: no server-side state
# beyond session vars is at stake and re-running cannot double-apply
_RETRYABLE_PREFIXES = ("select", "show", "explain", "desc", "use")


def _is_retryable_stmt(sql: str) -> bool:
    return sql.lstrip().lower().startswith(_RETRYABLE_PREFIXES)


class Client:
    RECONNECT_ATTEMPTS = 4

    def __init__(self, host: str = "127.0.0.1", port: int = 4000,
                 user: str = "root", password: str = "",
                 timeout: float = 30.0, ssl: bool = False,
                 ssl_ca: str = None, auto_reconnect: bool = True):
        self._params = (host, port, user, password, timeout)
        self._ssl = ssl
        self._ssl_ca = ssl_ca
        self.auto_reconnect = auto_reconnect
        self.seq = 0
        self.sock = None
        self._connect()

    def _connect(self) -> None:
        host, port, user, password, timeout = self._params
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.seq = 0
        try:
            self._handshake(user, password)
        except BaseException:
            self.sock.close()     # caller never gets a half-open client
            raise

    def _reconnect_with_backoff(self) -> None:
        delay = 0.05
        last = None
        for _ in range(self.RECONNECT_ATTEMPTS):
            try:
                self.sock.close()
            except Exception:  # noqa: BLE001
                pass
            try:
                self._connect()
                return
            except (OSError, ClientError) as e:
                last = e
                time.sleep(delay)
                delay *= 2
        raise ClientError(2013, f"reconnect failed: {last}")

    # -- framing -------------------------------------------------------------
    def _recv(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            part = self.sock.recv(n - len(buf))
            if not part:
                raise ClientError(2013, "server closed connection")
            buf += part
        return buf

    def _read_packet(self) -> bytes:
        h = self._recv(4)
        ln = h[0] | (h[1] << 8) | (h[2] << 16)
        self.seq = (h[3] + 1) & 0xFF
        return self._recv(ln) if ln else b""

    def _write_packet(self, payload: bytes) -> None:
        self.sock.sendall(struct.pack("<I", len(payload))[:3]
                          + bytes([self.seq]) + payload)
        self.seq = (self.seq + 1) & 0xFF

    @staticmethod
    def _lenenc(data: bytes, i: int) -> Tuple[int, int]:
        c = data[i]
        if c < 251:
            return c, i + 1
        if c == 0xFC:
            return data[i + 1] | (data[i + 2] << 8), i + 3
        if c == 0xFD:
            return int.from_bytes(data[i + 1:i + 4], "little"), i + 4
        return int.from_bytes(data[i + 1:i + 9], "little"), i + 9

    # -- protocol ------------------------------------------------------------
    def _handshake(self, user: str, password: str) -> None:
        g = self._read_packet()
        if g and g[0] == 0xFF:
            code = struct.unpack("<H", g[1:3])[0]
            raise ClientError(code, g[9:].decode(errors="replace"))
        i = g.index(b"\x00", 1) + 1
        i += 4
        salt = g[i:i + 8]
        srv_caps = (g[i + 9] | (g[i + 10] << 8)
                    | (g[i + 12 + 2] << 16) | (g[i + 12 + 3] << 24)) \
            if len(g) >= i + 16 else 0
        i += 9 + 2 + 1 + 2 + 2 + 1 + 10
        salt += g[i:i + 12]
        token = _scramble(password, salt)
        caps = 0x0200 | 0x8000 | 0x1
        if self._ssl and not (srv_caps & 0x800):
            raise ClientError(2026, "server does not support SSL")
        if self._ssl:
            caps |= 0x800                      # CLIENT_SSL
            # SSLRequest, then upgrade the transport before the real
            # handshake response (the server mirrors this order)
            self._write_packet(struct.pack("<I", caps)
                               + struct.pack("<I", 1 << 24)
                               + bytes([0xFF]) + b"\x00" * 23)
            import ssl as _ssl_mod
            if self._ssl_ca:
                ctx = _ssl_mod.create_default_context(
                    cafile=self._ssl_ca)
                ctx.check_hostname = False
            else:
                ctx = _ssl_mod.SSLContext(_ssl_mod.PROTOCOL_TLS_CLIENT)
                ctx.check_hostname = False
                ctx.verify_mode = _ssl_mod.CERT_NONE
            self.sock = ctx.wrap_socket(self.sock)
        resp = (struct.pack("<I", caps) + struct.pack("<I", 1 << 24)
                + bytes([0xFF]) + b"\x00" * 23
                + user.encode() + b"\x00"
                + bytes([len(token)]) + token)
        self._write_packet(resp)
        ok = self._read_packet()
        if ok[0] != 0x00:
            code = struct.unpack("<H", ok[1:3])[0]
            raise ClientError(code, ok[9:].decode(errors="replace"))

    def query(self, sql: str) -> Tuple[List[str], List[Tuple]]:
        """→ (column names, rows) for queries; ([], []) for OK packets.
        Every value arrives as str or None (text protocol)."""
        try:
            return self._query_once(sql)
        except (OSError, ClientError) as e:
            dead = isinstance(e, OSError) or \
                getattr(e, "code", None) == 2013
            if not (dead and self.auto_reconnect):
                raise
            self._reconnect_with_backoff()
            if not _is_retryable_stmt(sql):
                # fresh connection, but the statement's fate on the dead
                # one is unknowable — surface it instead of re-applying
                raise ClientError(
                    2013, "connection lost; statement not retried "
                          "(not read-only)") from e
            return self._query_once(sql)

    def _query_once(self, sql: str) -> Tuple[List[str], List[Tuple]]:
        self.seq = 0
        self._write_packet(b"\x03" + sql.encode())
        first = self._read_packet()
        if first[0] == 0xFF:
            code = struct.unpack("<H", first[1:3])[0]
            raise ClientError(code, first[9:].decode(errors="replace"))
        if first[0] == 0x00:
            # OK packet: header, affected rows, last insert id (lenenc)
            self.affected_rows, _ = self._lenenc(first, 1)
            return [], []
        ncols, _ = self._lenenc(first, 0)
        names = []
        for _ in range(ncols):
            col = self._read_packet()
            i = 0
            parts = []
            for _f in range(6):
                ln, i = self._lenenc(col, i)
                parts.append(col[i:i + ln])
                i += ln
            names.append(parts[4].decode())
        assert self._read_packet()[0] == 0xFE
        rows: List[Tuple] = []
        while True:
            pkt = self._read_packet()
            if pkt and pkt[0] == 0xFE and len(pkt) < 9:
                break
            i = 0
            row = []
            while i < len(pkt):
                if pkt[i] == 0xFB:
                    row.append(None)
                    i += 1
                else:
                    ln, i = self._lenenc(pkt, i)
                    row.append(pkt[i:i + ln].decode())
                    i += ln
            rows.append(tuple(row))
        return names, rows

    def execute(self, sql: str) -> int:
        """Send a statement that returns no rows → the rows it affected,
        as its OK packet states them."""
        self.affected_rows = 0
        self.query(sql)
        return self.affected_rows

    def close(self) -> None:
        try:
            self.seq = 0
            self._write_packet(b"\x01")
        except Exception:  # noqa: BLE001
            pass
        finally:
            self.sock.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
