"""Minimal MySQL-protocol client (text path and prepared statements).

The reference ships tools (dumpling, br) that reach the cluster through
stock MySQL drivers; no driver ships in this image, so this is the
in-repo equivalent — handshake with mysql_native_password, COM_QUERY
with text resultset decoding, and COM_STMT_PREPARE / EXECUTE / CLOSE with
binary parameters and binary result rows (`prepare`, `execute_prepared`).
Used by tidb_tpu.tools (dump/CSV CLIs) and available as a programmatic
driver for the wire server.

Resilience: with auto_reconnect (default on), a connection the server
closed (KILL <id>, restart) is re-established with exponential backoff
and the statement retried — but ONLY for read-only statements, where the
retry cannot double-apply work (go-sql-driver's ErrBadConn contract:
never auto-retry a write on an ambiguous connection death)."""

from __future__ import annotations

import datetime
import decimal
import hashlib
import socket
import struct
import time
from typing import List, Optional, Sequence, Tuple

from tidb_tpu.util.packetio import PacketReader


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


class Prepared:
    """A statement prepared on ONE connection (`Client.prepare`): the
    server's handle, its parameter count and, once known, the columns of
    its result."""

    __slots__ = ("stmt_id", "n_params", "names")

    def __init__(self, stmt_id: int, n_params: int, names: List[str]):
        self.stmt_id = stmt_id
        self.n_params = n_params
        self.names = names


def _lenenc_bytes(raw: bytes) -> bytes:
    n = len(raw)
    if n < 251:
        return bytes([n]) + raw
    if n < 1 << 16:
        return b"\xfc" + struct.pack("<H", n) + raw
    if n < 1 << 24:
        return b"\xfd" + struct.pack("<I", n)[:3] + raw
    return b"\xfe" + struct.pack("<Q", n) + raw


def _encode_param(v) -> Tuple[int, bytes]:
    """One bound parameter → (MySQL type, binary value), as libmysql's
    drivers send Python's types (ref: server/util.go parseExecArgs, the
    decoder on the other side). None is the caller's: it travels in the
    NULL bitmap."""
    if isinstance(v, bool):
        return 0x01, struct.pack("<b", int(v))                # TINY
    if isinstance(v, int):
        return 0x08, struct.pack("<q", v)                     # LONGLONG
    if isinstance(v, float):
        return 0x05, struct.pack("<d", v)                     # DOUBLE
    if isinstance(v, datetime.datetime):
        return 0x0C, bytes([7]) + struct.pack(
            "<HBBBBB", v.year, v.month, v.day, v.hour, v.minute, v.second)
    if isinstance(v, datetime.date):
        return 0x0A, bytes([4]) + struct.pack("<HBB", v.year, v.month,
                                              v.day)
    if isinstance(v, decimal.Decimal):
        return 0xF6, _lenenc_bytes(str(v).encode())           # NEWDECIMAL
    raw = v if isinstance(v, bytes) else str(v).encode("utf-8")
    return 0xFD, _lenenc_bytes(raw)                           # VAR_STRING


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
        # a command is one small packet and its answer is awaited at once:
        # Nagle's algorithm against the peer's delayed ACK would hold it
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.seq = 0
        # a reader of its own for every socket: what a dead connection
        # left in the old one's buffer is dropped with it
        self._reader = PacketReader(self.sock)
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
    def _read_packet(self) -> bytes:
        """The next packet, cut from the reader's buffer: one `recv`
        takes a whole result set's burst of packets (the server sends a
        response in one write)."""
        try:
            seq, payload = self._reader.read_packet()
        except ConnectionError as e:
            raise ClientError(2013, "server closed connection") from e
        self.seq = (seq + 1) & 0xFF
        return payload

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
            # nothing read ahead in the clear may be taken for an answer
            # that came under TLS
            self._reader = PacketReader(self.sock)
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

    def _raise_if_err(self, pkt: bytes) -> None:
        if pkt[0] == 0xFF:
            code = struct.unpack("<H", pkt[1:3])[0]
            raise ClientError(code, pkt[9:].decode(errors="replace"))

    def _read_coldefs(self, ncols: int) -> List[Tuple[str, int]]:
        """`ncols` column definitions and their EOF → [(name, MySQL
        type)]."""
        cols = []
        for _ in range(ncols):
            col = self._read_packet()
            i = 0
            parts = []
            for _f in range(6):
                ln, i = self._lenenc(col, i)
                parts.append(col[i:i + ln])
                i += ln
            # 0x0c, charset (2), display length (4), then the type
            cols.append((parts[4].decode(), col[i + 7]))
        assert self._read_packet()[0] == 0xFE
        return cols

    def _query_once(self, sql: str) -> Tuple[List[str], List[Tuple]]:
        self.seq = 0
        self._write_packet(b"\x03" + sql.encode())
        first = self._read_packet()
        self._raise_if_err(first)
        if first[0] == 0x00:
            # OK packet: header, affected rows, last insert id (lenenc)
            self.affected_rows, _ = self._lenenc(first, 1)
            return [], []
        ncols, _ = self._lenenc(first, 0)
        names = [name for name, _tp in self._read_coldefs(ncols)]
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

    def ping(self) -> None:
        """COM_PING → OK. A connection's commands are answered in order
        by one thread, so the answer also says that everything the
        command before it did there is done — its spans recorded, which
        happens after its response was sent."""
        self.seq = 0
        self._write_packet(b"\x0e")
        self._raise_if_err(self._read_packet())

    # -- prepared statements (binary protocol) ------------------------------
    def prepare(self, sql: str) -> Prepared:
        """COM_STMT_PREPARE → the handle `execute_prepared` takes. It
        lives on THIS connection: a reconnect (which `query` may make)
        forgets it, and the server then answers 1243."""
        self.seq = 0
        self._write_packet(b"\x16" + sql.encode())
        resp = self._read_packet()
        self._raise_if_err(resp)
        stmt_id, n_cols, n_params = struct.unpack("<IHH", resp[1:9])
        if n_params:
            self._read_coldefs(n_params)
        names = [n for n, _tp in self._read_coldefs(n_cols)] \
            if n_cols else []
        return Prepared(stmt_id, n_params, names)

    def execute_prepared(self, stmt: Prepared,
                         params: Sequence = ()) -> List[Tuple]:
        """COM_STMT_EXECUTE with `params` bound in order → the rows, read
        from the binary protocol: integers as int, FLOAT/DOUBLE as float,
        everything else (DECIMAL, DATE, DATETIME, TIME, strings) as the
        text `query` gives, NULL as None. A statement without a result
        set gives [] and sets `affected_rows`; the result's column names
        are on `stmt.names`. Sent once: never reconnected and retried."""
        if len(params) != stmt.n_params:
            raise ClientError(1210, f"statement takes {stmt.n_params} "
                                    f"parameter(s), {len(params)} given")
        body = struct.pack("<IBI", stmt.stmt_id, 0, 1)
        if params:
            bitmap = bytearray((len(params) + 7) // 8)
            types, values = b"", b""
            for i, p in enumerate(params):
                if p is None:
                    bitmap[i // 8] |= 1 << (i % 8)
                    types += b"\x06\x00"
                    continue
                tp, raw = _encode_param(p)
                types += bytes([tp, 0])
                values += raw
            body += bytes(bitmap) + b"\x01" + types + values
        self.seq = 0
        self._write_packet(b"\x17" + body)
        first = self._read_packet()
        self._raise_if_err(first)
        if first[0] == 0x00:
            self.affected_rows, _ = self._lenenc(first, 1)
            return []
        ncols, _ = self._lenenc(first, 0)
        cols = self._read_coldefs(ncols)
        stmt.names = [name for name, _tp in cols]
        types = [tp for _name, tp in cols]
        rows: List[Tuple] = []
        while True:
            pkt = self._read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return rows
            rows.append(self._binary_row(pkt, types))

    _INT_FORMATS = {0x01: "<b", 0x02: "<h", 0x03: "<i", 0x09: "<i",
                    0x08: "<q"}

    def _binary_row(self, pkt: bytes, types: List[int]) -> Tuple:
        """One binary-protocol row (ref: server/util.go dumpBinaryRow):
        0x00, a NULL bitmap offset by two bits, then the typed values."""
        i = 1 + (len(types) + 9) // 8
        row = []
        for ci, tp in enumerate(types):
            pos = ci + 2
            if pkt[1 + pos // 8] & (1 << (pos % 8)):
                row.append(None)
            elif tp in self._INT_FORMATS:
                fmt = self._INT_FORMATS[tp]
                row.append(struct.unpack_from(fmt, pkt, i)[0])
                i += struct.calcsize(fmt)
            elif tp in (0x04, 0x05):
                fmt = "<f" if tp == 0x04 else "<d"
                row.append(struct.unpack_from(fmt, pkt, i)[0])
                i += struct.calcsize(fmt)
            elif tp in (0x0A, 0x0C, 0x07):      # DATE / DATETIME / TIMESTAMP
                ln = pkt[i]
                y, mo, d = struct.unpack_from("<HBB", pkt, i + 1) \
                    if ln else (0, 0, 0)
                val = f"{y:04d}-{mo:02d}-{d:02d}"
                if ln >= 7:
                    val += " %02d:%02d:%02d" % tuple(pkt[i + 5:i + 8])
                row.append(val)
                i += 1 + ln
            elif tp == 0x0B:                    # TIME
                ln = pkt[i]
                val = "00:00:00"
                if ln:
                    days = struct.unpack_from("<I", pkt, i + 2)[0]
                    h, mi, sec = pkt[i + 6:i + 9]
                    val = f"{'-' if pkt[i + 1] else ''}" \
                          f"{days * 24 + h:02d}:{mi:02d}:{sec:02d}"
                row.append(val)
                i += 1 + ln
            else:                               # length-encoded text
                ln, i = self._lenenc(pkt, i)
                row.append(pkt[i:i + ln].decode())
                i += ln
        return tuple(row)

    def close_prepared(self, stmt: Prepared) -> None:
        """COM_STMT_CLOSE: the server forgets the handle and answers
        nothing (protocol)."""
        self.seq = 0
        self._write_packet(b"\x19" + struct.pack("<I", stmt.stmt_id))

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
