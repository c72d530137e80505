"""MySQL wire-protocol server (ref: server/conn.go:1021, server/util.go).

The reference's L1: a TCP listener speaking the MySQL client/server
protocol so stock clients and drivers connect. This implementation covers
both protocol paths the reference serves:

  * protocol-41 handshake v10 with real mysql_native_password challenge
    auth against the engine's user table (privilege/privileges cache.go
    analog in tidb_tpu/session/auth.py);
  * COM_QUERY → parse/plan/execute through a real Session, results as
    text resultsets (column definitions + length-encoded rows);
  * prepared statements (server/conn_stmt.go): COM_STMT_PREPARE binds
    `?` placeholders, COM_STMT_EXECUTE decodes binary parameters and
    returns BINARY resultset rows (server/util.go:237 dumpBinaryRow),
    COM_STMT_CLOSE / RESET / SEND_LONG_DATA;
  * COM_PING / COM_INIT_DB / COM_QUIT / COM_FIELD_LIST(no-op);
  * MySQL-coded error packets from the typed error hierarchy.

One OS thread per connection (threads spend their life blocked on recv or
inside numpy/XLA which release the GIL — the goroutine-per-conn shape of
clientConn.Run without an event loop). Every socket call hands the
interpreter lock to the other connections' threads, so a connection frames
its packets into a buffer and touches the socket once a response and once
a burst of arriving bytes (`_Conn`, packet framing)."""

from __future__ import annotations

import datetime
import decimal
import math
import hashlib
import os
import socket
import socketserver
import struct
import threading
import traceback
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.types import FieldType, TypeKind
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY
from tidb_tpu.util.packetio import BUFFER_BYTES, PacketReader

PROTOCOL_VERSION = 10
SERVER_VERSION = b"8.0.11-tidb-tpu"

# capability flags (include/mysql_com.h)
CLIENT_LONG_PASSWORD = 1
CLIENT_FOUND_ROWS = 1 << 1
CLIENT_LONG_FLAG = 1 << 2
CLIENT_CONNECT_WITH_DB = 1 << 3
CLIENT_PROTOCOL_41 = 1 << 9
CLIENT_TRANSACTIONS = 1 << 13
CLIENT_SSL = 1 << 11
CLIENT_SECURE_CONNECTION = 1 << 15
CLIENT_PLUGIN_AUTH = 1 << 19
CLIENT_MULTI_STATEMENTS = 1 << 16
CLIENT_MULTI_RESULTS = 1 << 17
CLIENT_DEPRECATE_EOF = 1 << 24

SERVER_CAPS = (CLIENT_LONG_PASSWORD | CLIENT_FOUND_ROWS | CLIENT_LONG_FLAG
               | CLIENT_CONNECT_WITH_DB | CLIENT_PROTOCOL_41
               | CLIENT_TRANSACTIONS | CLIENT_SECURE_CONNECTION
               | CLIENT_PLUGIN_AUTH | CLIENT_MULTI_STATEMENTS
               | CLIENT_MULTI_RESULTS)

SERVER_MORE_RESULTS_EXISTS = 0x0008

COM_QUIT = 0x01
COM_INIT_DB = 0x02
COM_QUERY = 0x03
COM_FIELD_LIST = 0x04
COM_PING = 0x0E
COM_STMT_PREPARE = 0x16
COM_STMT_EXECUTE = 0x17
COM_STMT_SEND_LONG_DATA = 0x18
COM_STMT_CLOSE = 0x19
COM_STMT_RESET = 0x1A

# MySQL column type codes (type → protocol byte)
_MYSQL_TYPE = {
    TypeKind.TINYINT: 0x01, TypeKind.SMALLINT: 0x02, TypeKind.INT: 0x03,
    TypeKind.BIGINT: 0x08, TypeKind.FLOAT: 0x04, TypeKind.DOUBLE: 0x05,
    TypeKind.DECIMAL: 0xF6, TypeKind.CHAR: 0xFE, TypeKind.VARCHAR: 0xFD,
    TypeKind.DATE: 0x0A, TypeKind.DATETIME: 0x0C, TypeKind.TIMESTAMP: 0x07,
    TypeKind.TIME: 0x0B, TypeKind.NULLTYPE: 0x06,
}


def _lenenc_int(n: int) -> bytes:
    if n < 251:
        return bytes([n])
    if n < 1 << 16:
        return b"\xfc" + struct.pack("<H", n)
    if n < 1 << 24:
        return b"\xfd" + struct.pack("<I", n)[:3]
    return b"\xfe" + struct.pack("<Q", n)


def _lenenc_str(s: bytes) -> bytes:
    return _lenenc_int(len(s)) + s


def _read_lenenc(data: bytes, i: int) -> Tuple[int, int]:
    c = data[i]
    if c < 251:
        return c, i + 1
    if c == 0xFC:
        return data[i + 1] | (data[i + 2] << 8), i + 3
    if c == 0xFD:
        return int.from_bytes(data[i + 1:i + 4], "little"), i + 4
    return int.from_bytes(data[i + 1:i + 9], "little"), i + 9


# ---------------------------------------------------------------------------
# Prepared statements (ref: server/conn_stmt.go, driver_stmt.go)
# ---------------------------------------------------------------------------


def _scan_segments(sql: str):
    """Yield (is_marker, text): the single tokenizer behind placeholder
    counting AND substitution — one scanner so the two can never disagree
    about what counts as a `?` (strings, quoted identifiers, and all
    three comment styles are opaque)."""
    i, L = 0, len(sql)
    start = 0
    while i < L:
        c = sql[i]
        if c in ("'", '"', "`"):
            q = c
            i += 1
            while i < L:
                if sql[i] == "\\" and q != "`":
                    i += 2
                    continue
                if sql[i] == q:
                    if i + 1 < L and sql[i + 1] == q:
                        i += 2
                        continue
                    i += 1
                    break
                i += 1
            continue
        if c == "-" and sql[i:i + 2] == "--":
            j = sql.find("\n", i)
            i = L if j < 0 else j + 1
            continue
        if c == "/" and sql[i:i + 2] == "/*":
            j = sql.find("*/", i + 2)
            i = L if j < 0 else j + 2
            continue
        if c == "#":
            j = sql.find("\n", i)
            i = L if j < 0 else j + 1
            continue
        if c == "?":
            if i > start:
                yield False, sql[start:i]
            yield True, "?"
            i += 1
            start = i
            continue
        i += 1
    if start < L:
        yield False, sql[start:]


def count_placeholders(sql: str) -> int:
    """`?` markers outside string literals, quoted identifiers, comments."""
    return sum(1 for is_marker, _ in _scan_segments(sql) if is_marker)


def substitute_placeholders(sql: str, values: List[object]) -> str:
    """Bind parameter values as SQL literals (the reference instead keeps
    params through plan-cache slots; textual binding is equivalent for
    correctness and reuses the whole parse/plan path)."""
    out = []
    vi = 0
    for is_marker, text in _scan_segments(sql):
        if is_marker:
            out.append(_sql_literal(values[vi]))
            vi += 1
        else:
            out.append(text)
    return "".join(out)


def _sql_literal(v) -> str:
    if v is None:
        return "NULL"
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, (int, float)):
        if isinstance(v, float) and not math.isfinite(v):
            return "NULL"     # MySQL has no inf/nan literals
        return repr(v)
    if isinstance(v, decimal.Decimal):
        return format(v, "f") if v.is_finite() else "NULL"
    if isinstance(v, bytes):
        v = v.decode("utf-8", "replace")
    if isinstance(v, (datetime.datetime, datetime.date)):
        v = str(v)
    s = str(v).replace("\\", "\\\\").replace("'", "\\'")
    return f"'{s}'"


class PreparedStmt:
    __slots__ = ("stmt_id", "sql", "n_params", "long_data", "param_types")

    def __init__(self, stmt_id: int, sql: str):
        self.stmt_id = stmt_id
        self.sql = sql
        self.n_params = count_placeholders(sql)
        self.long_data: Dict[int, bytes] = {}
        # cached from the first execute: C-client drivers send parameter
        # types only when bindings change (new_params_bound_flag)
        self.param_types: Optional[List[Tuple[int, bool]]] = None


# binary protocol parameter decoding (ref: server/util.go parseExecArgs)
def decode_binary_params(data: bytes, i: int, stmt: "PreparedStmt"
                         ) -> List[object]:
    n_params = stmt.n_params
    long_data = stmt.long_data
    null_bytes = (n_params + 7) // 8
    null_bitmap = data[i:i + null_bytes]
    i += null_bytes
    new_bound = data[i]
    i += 1
    types: List[Tuple[int, bool]] = []
    if new_bound:
        for _ in range(n_params):
            tp = data[i]
            unsigned = bool(data[i + 1] & 0x80)
            types.append((tp, unsigned))
            i += 2
        stmt.param_types = types
    elif stmt.param_types is not None:
        types = stmt.param_types
    else:
        raise TiDBTPUError("COM_STMT_EXECUTE without parameter types")
    vals: List[object] = []
    for p, (tp, unsigned) in enumerate(types):
        if null_bitmap[p // 8] & (1 << (p % 8)):
            vals.append(None)
            continue
        if p in long_data:
            vals.append(long_data[p])
            continue
        if tp == 0x01:      # TINY
            v = data[i]
            i += 1
            vals.append(v if unsigned else (v - 256 if v > 127 else v))
        elif tp == 0x02:    # SHORT
            v = struct.unpack_from("<H" if unsigned else "<h", data, i)[0]
            i += 2
            vals.append(v)
        elif tp in (0x03, 0x09):   # LONG / INT24
            v = struct.unpack_from("<I" if unsigned else "<i", data, i)[0]
            i += 4
            vals.append(v)
        elif tp == 0x08:    # LONGLONG
            v = struct.unpack_from("<Q" if unsigned else "<q", data, i)[0]
            i += 8
            vals.append(v)
        elif tp == 0x04:    # FLOAT
            v = struct.unpack_from("<f", data, i)[0]
            i += 4
            vals.append(v)
        elif tp == 0x05:    # DOUBLE
            v = struct.unpack_from("<d", data, i)[0]
            i += 8
            vals.append(v)
        elif tp in (0x0A, 0x0C, 0x07):   # DATE/DATETIME/TIMESTAMP
            ln = data[i]
            i += 1
            if ln == 0:
                vals.append("0000-00-00")
            else:
                y, mo, d = struct.unpack_from("<HBB", data, i)
                h = mi = s = 0
                if ln >= 7:
                    h, mi, s = data[i + 4], data[i + 5], data[i + 6]
                i += ln
                if tp == 0x0A and ln == 4:
                    vals.append(f"{y:04d}-{mo:02d}-{d:02d}")
                else:
                    vals.append(f"{y:04d}-{mo:02d}-{d:02d} "
                                f"{h:02d}:{mi:02d}:{s:02d}")
        elif tp == 0x0B:    # TIME
            ln = data[i]
            i += 1
            if ln == 0:
                vals.append("00:00:00")
            else:
                neg = data[i]
                days = struct.unpack_from("<I", data, i + 1)[0]
                h, mi, s = data[i + 5], data[i + 6], data[i + 7]
                i += ln
                sign = "-" if neg else ""
                vals.append(f"{sign}{days * 24 + h:02d}:{mi:02d}:{s:02d}")
        elif tp == 0x06:    # NULL
            vals.append(None)
        elif tp in (0x00, 0xF6):    # DECIMAL / NEWDECIMAL: a NUMBER that
            # travels as length-encoded text; bound as a quoted string it
            # would compare as one
            ln, i = _read_lenenc(data, i)
            vals.append(decimal.Decimal(data[i:i + ln].decode("ascii")))
            i += ln
        else:               # strings / blobs: length-encoded
            ln, i = _read_lenenc(data, i)
            vals.append(data[i:i + ln].decode("utf-8", "replace"))
            i += ln
    return vals


# binary resultset value encoding (ref: server/util.go dumpBinaryRow)
def _encode_binary_value(v, ft: FieldType) -> bytes:
    k = ft.kind
    if k in (TypeKind.TINYINT,):
        return struct.pack("<b", int(v))
    if k is TypeKind.SMALLINT:
        return struct.pack("<h", int(v))
    if k is TypeKind.INT:
        return struct.pack("<i", int(v))
    if k is TypeKind.BIGINT:
        return struct.pack("<q", int(v))
    if k is TypeKind.FLOAT:
        return struct.pack("<f", float(v))
    if k is TypeKind.DOUBLE:
        return struct.pack("<d", float(v))
    if k in (TypeKind.DATE, TypeKind.DATETIME, TypeKind.TIMESTAMP):
        s = str(v)
        y, mo, d = int(s[0:4]), int(s[5:7]), int(s[8:10])
        if len(s) > 10:
            h, mi, sec = int(s[11:13]), int(s[14:16]), int(s[17:19])
            return bytes([7]) + struct.pack("<HBBBBB", y, mo, d, h, mi, sec)
        return bytes([4]) + struct.pack("<HBB", y, mo, d)
    if k is TypeKind.TIME:
        s = str(v)
        neg = s.startswith("-")
        if neg:
            s = s[1:]
        parts = s.split(":")
        h, mi = int(parts[0]), int(parts[1])
        sec = int(float(parts[2])) if len(parts) > 2 else 0
        return bytes([8, 1 if neg else 0]) + struct.pack(
            "<IBBB", h // 24, h % 24, mi, sec)
    # decimals and strings travel as length-encoded text
    return _lenenc_str(_text_value(v))


# ---------------------------------------------------------------------------
# mysql_native_password (ref: privilege auth; server/auth.go)
# ---------------------------------------------------------------------------


def native_password_verify(salt: bytes, token: bytes, stage2: bytes) -> bool:
    """token = SHA1(pw) XOR SHA1(salt + SHA1(SHA1(pw))); server stores
    stage2 = SHA1(SHA1(pw)). Recover SHA1(pw) and re-hash to compare."""
    if not token:
        return stage2 == b""           # empty password
    if len(token) != 20 or stage2 == b"":
        return False
    mix = hashlib.sha1(salt + stage2).digest()
    sha_pw = bytes(a ^ b for a, b in zip(token, mix))
    return hashlib.sha1(sha_pw).digest() == stage2


class _Conn:
    """One client connection (ref: clientConn in server/conn.go)."""

    def __init__(self, sock: socket.socket, engine, conn_id: int,
                 ssl_ctx=None):
        self.sock = sock
        self.engine = engine
        self.session = engine.new_session()
        # advertise the SESSION's conn id in the handshake, so the id a
        # client reads (CONNECTION_ID, or the greeting) is the same id
        # SHOW PROCESSLIST prints and KILL resolves — `conn_id` from the
        # listener is just an accept counter
        self.conn_id = self.session.conn_id
        self.seq = 0
        self.ssl_ctx = ssl_ctx
        self.caps = SERVER_CAPS | (CLIENT_SSL if ssl_ctx else 0)
        self.stmts: Dict[int, PreparedStmt] = {}
        self._next_stmt_id = 0
        # exact reads until `handshake` returns: bytes that follow an
        # SSLRequest are the TLS layer's, and a read ahead would swallow
        # them (`run` switches it on)
        self._reader = PacketReader(sock, ahead=False)
        self._out = bytearray()         # framed, not yet on the socket
        self._out_packets = 0
        self.sends = 0                  # socket calls made to send,
        self.packets_sent = 0           # and the packets they carried

    # -- packet framing ------------------------------------------------------
    # (ref: server/packetio.go: buffered reader and writer, flushed when a
    # command's response ends.) Packets are framed into `_out`, which goes
    # to the socket (a) when a command's response ends, inside its
    # `wire.write` span, (b) when it passes BUFFER_BYTES, so a large result
    # set streams and a connection's memory stays bounded, and (c) ALWAYS
    # before the thread blocks in a read: whatever path framed a packet
    # (OK, ERR, the greeting, a response cut short by an error), the peer
    # has it before this end waits for the peer. The bytes and their order
    # are what one `sendall` a packet gave; only the calls differ.
    def read_packet(self) -> bytes:
        self.flush()
        rd = self._reader
        seq, payload = rd.read_packet()
        if rd.recvs:
            # counted once a burst, never once a packet: a packet cut
            # from the buffer is counted with the next burst
            REGISTRY.inc("tidb_tpu_wire_socket_calls_total",
                         {"kind": "recv"}, by=rd.recvs)
            REGISTRY.inc("tidb_tpu_wire_packets_total", {"kind": "recv"},
                         by=rd.packets)
            rd.recvs = rd.packets = 0
        self.seq = (seq + 1) & 0xFF
        return payload

    def write_packet(self, payload: bytes) -> None:
        out = b""
        while True:
            part = payload[: 0xFFFFFF]
            payload = payload[0xFFFFFF:]
            out += struct.pack("<I", len(part))[:3] + bytes([self.seq])
            out += part
            self.seq = (self.seq + 1) & 0xFF
            if len(part) < 0xFFFFFF:
                break
        self._queue(out, 1)

    def _queue(self, framed: bytes, packets: int) -> None:
        """Framed packets join the buffer. A piece of BUFFER_BYTES or more
        (a native chunk of rows) follows what precedes it directly,
        uncopied."""
        if len(framed) >= BUFFER_BYTES:
            self.flush()
            self._send(framed, packets)
            return
        self._out += framed
        self._out_packets += packets
        if len(self._out) >= BUFFER_BYTES:
            self.flush()

    def flush(self) -> None:
        if self._out:
            out, packets = self._out, self._out_packets
            self._out, self._out_packets = bytearray(), 0
            self._send(out, packets)

    def _send(self, data, packets: int) -> None:
        # counted as the call is made: whoever holds the response finds
        # it counted
        self.sends += 1
        self.packets_sent += packets
        REGISTRY.inc("tidb_tpu_wire_socket_calls_total", {"kind": "send"})
        REGISTRY.inc("tidb_tpu_wire_packets_total", {"kind": "send"},
                     by=packets)
        self.sock.sendall(data)

    @contextmanager
    def _response(self):
        """The `wire.write` span of one command's response: row encoding,
        framing and the send, tagged with the socket calls it made and the
        packets they carried (`packets` ÷ `sends` is what buffering buys:
        1 with a send a packet, 7 for a one-row result set in one)."""
        with timeline.span("wire.write", "wire"):
            sends, packets = self.sends, self.packets_sent
            yield
            self.flush()
            timeline.tag(sends=self.sends - sends,
                         packets=self.packets_sent - packets)

    # -- generic packets -----------------------------------------------------
    def write_ok(self, affected: int = 0, insert_id: int = 0,
                 status: int = 0x0002) -> None:
        self.write_packet(b"\x00" + _lenenc_int(affected)
                          + _lenenc_int(insert_id)
                          + struct.pack("<HH", status, 0))

    def write_eof(self, status: int = 0x0002) -> None:
        self.write_packet(b"\xfe" + struct.pack("<HH", 0, status))

    def write_err(self, code: int, msg: str, state: bytes = b"HY000"):
        self.write_packet(b"\xff" + struct.pack("<H", code) + b"#" + state
                          + msg.encode("utf-8", "replace")[:512])

    # -- handshake -----------------------------------------------------------
    def handshake(self) -> None:
        # random 20-byte printable nonzero salt (protocol requirement)
        salt = bytes((b % 93) + 33 for b in os.urandom(20))
        greeting = (
            bytes([PROTOCOL_VERSION]) + SERVER_VERSION + b"\x00"
            + struct.pack("<I", self.conn_id)
            + salt[:8] + b"\x00"
            + struct.pack("<H", self.caps & 0xFFFF)
            + bytes([0xFF])                        # charset utf8
            + struct.pack("<H", 0x0002)            # status: autocommit
            + struct.pack("<H", self.caps >> 16)
            + bytes([21])                          # auth data len
            + b"\x00" * 10
            + salt[8:] + b"\x00"
            + b"mysql_native_password\x00")
        self.seq = 0
        self.write_packet(greeting)
        resp = self.read_packet()
        if self.ssl_ctx is not None and len(resp) >= 4 and \
                struct.unpack("<I", resp[:4])[0] & CLIENT_SSL:
            # SSLRequest: upgrade the transport, then read the real
            # handshake response over TLS (server/conn.go TLS branch)
            self.sock = self.ssl_ctx.wrap_socket(self.sock,
                                                 server_side=True)
            self._reader = PacketReader(self.sock, ahead=False)
            resp = self.read_packet()
        if len(resp) < 32:
            raise ConnectionError("malformed handshake response")
        self.caps = struct.unpack("<I", resp[:4])[0]
        # skip max packet (4) + charset (1) + filler (23)
        i = 32
        end = resp.index(b"\x00", i)
        user = resp[i:end].decode("utf-8", "replace")
        i = end + 1
        token = b""
        if self.caps & CLIENT_SECURE_CONNECTION and i < len(resp):
            alen = resp[i]
            token = resp[i + 1:i + 1 + alen]
            i += 1 + alen
        if self.caps & CLIENT_CONNECT_WITH_DB and i < len(resp) and \
                b"\x00" in resp[i:]:
            end = resp.index(b"\x00", i)
            _db = resp[i:end]
        # mysql_native_password challenge verification against the
        # engine's user table (cache.go analog); unknown user or bad
        # scramble → ER_ACCESS_DENIED_ERROR
        stage2 = self.engine.auth.stage2(user)
        if stage2 is None or not native_password_verify(salt, token,
                                                        stage2):
            self.write_err(1045, f"Access denied for user '{user}'@'%' "
                                 f"(using password: "
                                 f"{'YES' if token else 'NO'})",
                           b"28000")
            raise ConnectionError("auth failed")
        self.session.user = user.lower()
        self.write_ok()

    # -- results -------------------------------------------------------------
    def _coldef(self, name: str, ft: FieldType) -> bytes:
        tp = _MYSQL_TYPE.get(ft.kind, 0xFD)
        flags = 0 if ft.nullable else 0x0001       # NOT_NULL_FLAG
        return (_lenenc_str(b"def") + _lenenc_str(b"") + _lenenc_str(b"")
                + _lenenc_str(b"") + _lenenc_str(name.encode())
                + _lenenc_str(name.encode()) + b"\x0c"
                + struct.pack("<H", 0xFF)          # charset
                + struct.pack("<I", 1024)          # display length
                + bytes([tp]) + struct.pack("<H", flags)
                + bytes([ft.scale & 0xFF]) + b"\x00\x00")

    def write_resultset(self, names: List[str], ftypes: List[FieldType],
                        rows: List[tuple], status: int = 0x0002,
                        chunks=None) -> None:
        self.write_packet(_lenenc_int(len(names)))
        for nm, ft in zip(names, ftypes):
            self.write_packet(self._coldef(nm, ft))
        self.write_eof()
        if chunks is not None:
            # columnar fast path: the whole batch encodes to framed row
            # packets in C++ (tidb_tpu/native/rowcodec.cpp — the native
            # dumpTextRow of server/util.go:390)
            from tidb_tpu import native
            for ch in chunks:
                if ch.num_rows == 0:
                    continue
                enc = native.encode_text_rows(ch, ftypes, self.seq)
                if enc is None:
                    self._write_rows_python(ch.rows())
                    continue
                payload, self.seq = enc
                self._queue(payload, ch.num_rows)
        else:
            self._write_rows_python(rows)
        self.write_eof(status)

    def _write_rows_python(self, rows) -> None:
        for row in rows:
            out = b""
            for v in row:
                if v is None:
                    out += b"\xfb"
                else:
                    out += _lenenc_str(_text_value(v))
            self.write_packet(out)

    # -- command loop --------------------------------------------------------
    def run(self) -> None:
        try:
            self.handshake()
            self._reader.ahead = True
            self._serve()
        finally:
            # whatever ends the connection, what was framed for the peer
            # leaves first: the ERR of a failed handshake, the response
            # of the command a KILL arrived beside
            try:
                self.flush()
            except OSError:
                pass                # the peer is gone

    def _serve(self) -> None:
        while True:
            self.seq = 0
            try:
                # the server waits here for the client: device time idle
                # under this span is the client's, not the server's
                with timeline.span("client.wait", "client",
                                   pid=self.conn_id, wait="socket"):
                    pkt = self.read_packet()
            except ConnectionError:
                return
            if not pkt:
                return
            cmd, data = pkt[0], pkt[1:]
            if cmd == COM_QUIT:
                return
            from tidb_tpu.util.guard import PROCESS_REGISTRY
            if PROCESS_REGISTRY.conn_killed(self.session.conn_id):
                # killed while idle: drop the socket without answering —
                # the client observes a dead connection (2013), exactly
                # what stock drivers expect after killConn
                return
            try:
                if cmd == COM_PING:
                    self.write_ok()
                elif cmd == COM_INIT_DB:
                    self.write_ok()
                elif cmd == COM_FIELD_LIST:
                    self.write_eof()
                elif cmd == COM_QUERY:
                    self._request(self._query, data, "text")
                elif cmd == COM_STMT_PREPARE:
                    self._stmt_prepare(data.decode("utf-8", "replace"))
                elif cmd == COM_STMT_EXECUTE:
                    self._request(self._stmt_execute, data, "binary")
                elif cmd == COM_STMT_CLOSE:
                    self.stmts.pop(struct.unpack("<I", data[:4])[0], None)
                    # COM_STMT_CLOSE sends no response (protocol)
                elif cmd == COM_STMT_RESET:
                    st = self.stmts.get(struct.unpack("<I", data[:4])[0])
                    if st is not None:
                        st.long_data.clear()
                    self.write_ok()
                elif cmd == COM_STMT_SEND_LONG_DATA:
                    sid, pidx = struct.unpack("<IH", data[:6])
                    st = self.stmts.get(sid)
                    if st is not None:
                        st.long_data[pidx] = st.long_data.get(pidx, b"") + \
                            data[6:]
                    # no response (protocol)
                else:
                    self.write_err(1047, f"unknown command {cmd}",
                                   b"08S01")
            except TiDBTPUError as e:
                self.write_err(getattr(e, "code", 1105), str(e))
            except Exception as e:  # noqa: BLE001 — conn must not die
                traceback.print_exc()
                self.write_err(1105, f"{type(e).__name__}: {e}")
            # bare KILL <id> poisons the registry entry; close the socket
            # after the current command's response is on the wire (the
            # reference's killConn — clients observe 2013 on next use)
            from tidb_tpu.util.guard import PROCESS_REGISTRY
            if PROCESS_REGISTRY.conn_killed(self.session.conn_id):
                return

    def _request(self, handler, data: bytes, proto: str) -> None:
        """One request, command received → last result byte written: mint
        its id, keep it on the session while the request runs, and hold
        the timeline's `stmt` root span around the handler, tagged with
        the protocol the command came in (`proto=text|binary`)."""
        self.session._request_id = rid = timeline.new_request_id()
        try:
            with timeline.span("stmt", "stmt", pid=self.conn_id, req=rid,
                               proto=proto):
                handler(data)
        finally:
            self.session._request_id = 0

    # -- prepared statements (ref: server/conn_stmt.go) ----------------------
    def _stmt_prepare(self, sql: str) -> None:
        """COM_STMT_PREPARE with REAL result-set metadata (ref:
        server/conn_stmt.go writePrepare): the statement is planned once
        at prepare time with parameters bound to NULL, so strict binary-
        protocol clients get true column count and definitions up front.
        Parameters still type as VARCHAR (the reference also defers
        param inference to EXECUTE for most types). The probe is CHEAP
        by construction (session.plan_for_prepare): subquery evaluation
        and plan-cache insertion are disabled, so preparing a statement
        never executes user reads and never pollutes the plan cache
        with NULL-substituted parameter text. Statements whose metadata
        would require running subqueries, or that only plan with
        concrete values, fall back to 0 columns."""
        self._next_stmt_id += 1
        st = PreparedStmt(self._next_stmt_id, sql)
        self.stmts[st.stmt_id] = st
        names, ftypes = [], []
        try:
            from tidb_tpu.parser import ast as _ast
            from tidb_tpu.parser import parse as _parse
            probe = substitute_placeholders(sql, [None] * st.n_params)
            stmt = _parse(probe)[0]
            if isinstance(stmt, (_ast.SelectStmt, _ast.SetOpStmt)):
                plan = self.session.plan_for_prepare(stmt)
                if plan is not None:
                    names = [c.name for c in plan.schema.columns]
                    ftypes = list(plan.schema.field_types)
        except Exception:  # noqa: BLE001 — metadata is best-effort
            names, ftypes = [], []
        # response: [OK, stmt_id, n_cols, n_params, 0, warnings]
        self.write_packet(b"\x00" + struct.pack("<IHH", st.stmt_id,
                                                len(names), st.n_params)
                          + b"\x00" + struct.pack("<H", 0))
        if st.n_params:
            from tidb_tpu import types as T
            for p in range(st.n_params):
                self.write_packet(self._coldef(f"?{p}", T.varchar()))
            self.write_eof()
        if names:
            for nm, ft in zip(names, ftypes):
                self.write_packet(self._coldef(nm, ft))
            self.write_eof()

    def _stmt_execute(self, data: bytes) -> None:
        sid = struct.unpack("<I", data[:4])[0]
        st = self.stmts.get(sid)
        if st is None:
            self.write_err(1243, f"Unknown prepared statement handler "
                                 f"({sid}) given to EXECUTE", b"HY000")
            return
        with timeline.span("wire.read", "wire", params=st.n_params):
            # flags (1) + iteration count (4)
            i = 9
            params: List[object] = []
            if st.n_params:
                params = decode_binary_params(data, i, st)
            sql = substitute_placeholders(st.sql, params)
        # COM_STMT_EXECUTE admissions classify as interactive in the
        # priority scheduler regardless of statement shape
        results = self.session.execute(sql, from_prepared=True)
        with self._response():
            for k, rs in enumerate(results):
                status = 0x0002 | (SERVER_MORE_RESULTS_EXISTS
                                   if k + 1 < len(results) else 0)
                if rs.is_query:
                    self._write_binary_resultset(rs.names, rs.ftypes,
                                                 rs.rows, status)
                else:
                    self.write_ok(affected=rs.affected_rows, status=status)

    def _write_binary_resultset(self, names: List[str],
                                ftypes: List[FieldType],
                                rows: List[tuple], status: int) -> None:
        """Binary-protocol resultset (server/util.go:237 dumpBinaryRow):
        0x00 header, NULL bitmap with 2-bit offset, typed values."""
        self.write_packet(_lenenc_int(len(names)))
        for nm, ft in zip(names, ftypes):
            self.write_packet(self._coldef(nm, ft))
        self.write_eof()
        ncols = len(names)
        nb = (ncols + 9) // 8
        for row in rows:
            bitmap = bytearray(nb)
            body = b""
            for ci, (v, ft) in enumerate(zip(row, ftypes)):
                if v is None:
                    pos = ci + 2
                    bitmap[pos // 8] |= 1 << (pos % 8)
                else:
                    body += _encode_binary_value(v, ft)
            self.write_packet(b"\x00" + bytes(bitmap) + body)
        self.write_eof(status)

    def _query(self, data: bytes) -> None:
        with timeline.span("wire.read", "wire"):
            sql = data.decode("utf-8", "replace")
        results = self.session.execute(sql)
        with self._response():
            for i, rs in enumerate(results):
                # non-final resultsets carry SERVER_MORE_RESULTS_EXISTS so
                # drivers keep reading (multi-statement COM_QUERY)
                status = 0x0002 | (SERVER_MORE_RESULTS_EXISTS
                                   if i + 1 < len(results) else 0)
                if rs.is_query:
                    # pass rows=None when chunks exist: ResultSet.rows is
                    # a LAZY property and touching it would decode every
                    # row
                    self.write_resultset(
                        rs.names, rs.ftypes,
                        None if rs.chunks is not None else rs.rows,
                        status, chunks=rs.chunks)
                else:
                    self.write_ok(affected=rs.affected_rows, status=status)


def _text_value(v) -> bytes:
    if isinstance(v, bool):
        return b"1" if v else b"0"
    if isinstance(v, bytes):
        return v
    if isinstance(v, float):
        return repr(v).encode()
    return str(v).encode("utf-8")


#: allocations (less deallocations) between young-generation collections
#: while a server of this process is serving; the interpreter's default is
#: 700
GC_YOUNG_THRESHOLD = 100_000
_GC_LOCK = threading.Lock()
_GC_SERVING = 0                 # servers started and not yet stopped
_GC_BEFORE: Optional[tuple] = None


def _tune_gc(serving: bool) -> None:
    """While it serves, a process raises the collector's young threshold
    (as TiDB's server tunes GOGC at start), and the last server to stop
    puts back what it found: an embedding process that only builds an
    Engine, or has stopped its server, keeps its own collector. A
    statement's tokens, parse tree and plan are thousands of objects that
    all die by reference count when it ends — a 600-row INSERT makes
    ≈ 25,000. At 700 they are collected dozens of times while still alive
    and promoted, and every few statements their promotion triggers a FULL
    collection over everything the process holds (50–80 ms of one
    statement in five of a refresh stream, measured)."""
    import gc
    global _GC_SERVING, _GC_BEFORE
    with _GC_LOCK:
        _GC_SERVING += 1 if serving else -1
        if serving and _GC_SERVING == 1:
            _GC_BEFORE = gc.get_threshold()
            if 0 < _GC_BEFORE[0] < GC_YOUNG_THRESHOLD:
                gc.set_threshold(GC_YOUNG_THRESHOLD, *_GC_BEFORE[1:])
        elif not serving and _GC_SERVING == 0 and _GC_BEFORE is not None:
            gc.set_threshold(*_GC_BEFORE)
            _GC_BEFORE = None


class Server:
    """TCP front end over one Engine (ref: server/server.go)."""

    def __init__(self, engine=None, host: str = "127.0.0.1",
                 port: int = 4000, ssl_cert: Optional[str] = None,
                 ssl_key: Optional[str] = None):
        from tidb_tpu.session import Engine
        self.engine = engine or Engine()
        self._next_conn = 0
        self._lock = threading.Lock()
        self._ssl_ctx = None
        if ssl_cert and ssl_key:
            import ssl as _ssl
            self._ssl_ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
            self._ssl_ctx.load_cert_chain(ssl_cert, ssl_key)
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                # a result set that outgrows the connection's buffer
                # leaves in several writes and the client answers none
                # of them: without this each waits for the peer's
                # delayed ACK (Nagle), 40 ms a statement
                self.request.setsockopt(socket.IPPROTO_TCP,
                                        socket.TCP_NODELAY, 1)
                with outer._lock:
                    outer._next_conn += 1
                    cid = outer._next_conn
                conn = _Conn(self.request, outer.engine, cid,
                             outer._ssl_ctx)
                try:
                    conn.run()
                except (ConnectionError, OSError):
                    pass

        class TCP(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = TCP((host, port), Handler)
        self.port = self._srv.server_address[1]
        self._thread: Optional[threading.Thread] = None
        self._serving = False

    def start(self) -> "Server":
        _tune_gc(True)
        self._serving = True
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._serving:
            self._serving = False
            _tune_gc(False)
        self._srv.shutdown()
        self._srv.server_close()
