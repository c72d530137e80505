"""Buffered packet I/O on both ends of a connection (`server._Conn`,
`client.Client`, `util/packetio.py`): the BYTES a connection carries are
what one `sendall` a packet gave — checked against a plain framer below
that builds every response packet by packet — and only the number of
socket calls differs: one write a response, one read a burst."""

import datetime
import decimal
import os
import socket
import ssl
import struct
import subprocess
import threading
import time

import pytest

from tidb_tpu import server as srv_mod
from tidb_tpu.client import Client, ClientError
from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.server import Server
from tidb_tpu.session import Engine
from tidb_tpu.util.observability import REGISTRY
from tidb_tpu.util.packetio import BUFFER_BYTES

ROWS = [(1, "one", decimal.Decimal("1.50"), 0.5, datetime.date(2024, 1, 2)),
        (2, None, None, None, None),
        (3, "three", decimal.Decimal("-3.25"), 3.0,
         datetime.date(1999, 12, 31))]
MORE = 0x0008                       # SERVER_MORE_RESULTS_EXISTS
BIGINT, DOUBLE, DATE, DECIMAL, VARCHAR = 0x08, 0x05, 0x0A, 0xF6, 0xFD


# -- the plain framer: the protocol as the manual gives it, nothing shared
# -- with the code under test -------------------------------------------------

def lenenc(n):
    if n < 251:
        return bytes([n])
    if n < 1 << 16:
        return b"\xfc" + n.to_bytes(2, "little")
    return b"\xfd" + n.to_bytes(3, "little")


def lenstr(b):
    return lenenc(len(b)) + b


def coldef(name, tp, not_null=False, scale=0):
    nm = name.encode()
    return (lenstr(b"def") + lenstr(b"") * 3 + lenstr(nm) * 2 + b"\x0c"
            + (0xFF).to_bytes(2, "little") + (1024).to_bytes(4, "little")
            + bytes([tp]) + (1 if not_null else 0).to_bytes(2, "little")
            + bytes([scale]) + b"\x00\x00")


def eof(status=0):
    return b"\xfe\x00\x00" + (0x0002 | status).to_bytes(2, "little")


def ok(affected=0, status=0):
    return (b"\x00" + lenenc(affected) + lenenc(0)
            + (0x0002 | status).to_bytes(2, "little") + b"\x00\x00")


def err(code, msg, state=b"HY000"):
    return (b"\xff" + code.to_bytes(2, "little") + b"#" + state
            + msg.encode("utf-8", "replace")[:512])


def text_row(*cells):
    return b"".join(b"\xfb" if c is None else lenstr(c.encode())
                    for c in cells)


def frame(payloads, seq=1):
    out = b""
    for p in payloads:
        out += len(p).to_bytes(3, "little") + bytes([seq & 0xFF]) + p
        seq += 1
    return out


WB_COLS = [coldef("k", BIGINT, not_null=True), coldef("v", VARCHAR),
           coldef("d", DECIMAL, scale=2), coldef("f", DOUBLE),
           coldef("dt", DATE)]
SHOW_COLS = [coldef(n, VARCHAR)
             for n in ("Field", "Type", "Null", "Key", "Default")]
SHOW_ROWS = [text_row("k", "bigint not null", "NO", "", None),
             text_row("v", "varchar(16)", "YES", "", None),
             text_row("d", "decimal(10,2)", "YES", "", None),
             text_row("f", "double", "YES", "", None),
             text_row("dt", "date", "YES", "", None)]


def execute_cmd(stmt_id, key):
    """COM_STMT_EXECUTE of a one-parameter statement with a BIGINT."""
    return (b"\x17" + struct.pack("<IBI", stmt_id, 0, 1) + b"\x00\x01"
            + bytes([BIGINT, 0]) + struct.pack("<q", key))


def binary_row(k, v, d, f, dt):
    cells = [struct.pack("<q", k),
             None if v is None else lenstr(v.encode()),
             None if d is None else lenstr(str(d).encode()),
             None if f is None else struct.pack("<d", f),
             None if dt is None else b"\x04" + struct.pack(
                 "<HBB", dt.year, dt.month, dt.day)]
    bitmap = bytearray((len(cells) + 9) // 8)
    for i, c in enumerate(cells):
        if c is None:
            bitmap[(i + 2) // 8] |= 1 << ((i + 2) % 8)
    return b"\x00" + bytes(bitmap) + b"".join(c for c in cells
                                              if c is not None)


# -- a connection that shows its bytes ----------------------------------------

class Raw:
    """A socket after the handshake: `command` sends one command packet,
    `expect` reads exactly as many bytes as the expected response has."""

    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        greeting = self._packet()
        assert greeting[0] == 10
        caps = 0x0200 | 0x8000 | 0x1 | (1 << 16)     # + MULTI_STATEMENTS
        self.sock.sendall(frame([struct.pack("<I", caps)
                                 + struct.pack("<I", 1 << 24) + b"\xff"
                                 + b"\x00" * 23 + b"root\x00\x00"]))
        assert self._packet()[0] == 0x00

    def read(self, n):
        buf = b""
        while len(buf) < n:
            part = self.sock.recv(n - len(buf))
            assert part, f"server closed after {buf!r}"
            buf += part
        return buf

    def _packet(self):
        h = self.read(4)
        return self.read(int.from_bytes(h[:3], "little"))

    def command(self, payload):
        self.sock.sendall(frame([payload], seq=0))

    def expect(self, packets):
        want = frame(packets)
        assert self.read(len(want)) == want

    def close(self):
        self.sock.close()


@pytest.fixture(scope="module")
def served():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE wb (k BIGINT NOT NULL, v VARCHAR(16), "
              "d DECIMAL(10,2), f DOUBLE, dt DATE, PRIMARY KEY (k))")
    s.execute("INSERT INTO wb VALUES (1,'one',1.50,0.5,'2024-01-02'),"
              "(2,NULL,NULL,NULL,NULL),(3,'three',-3.25,3.0,'1999-12-31')")
    s.execute("CREATE TABLE wlog (n BIGINT)")
    s.execute("CREATE TABLE big (n BIGINT NOT NULL, pad VARCHAR(64))")
    s.execute("INSERT INTO big VALUES " + ",".join(
        f"({i}, '{'x' * 40}{i:08d}')" for i in range(12_000)))
    server = Server(eng, port=0).start()
    yield server
    server.stop()


@pytest.fixture
def raw(served):
    r = Raw(served.port)
    yield r
    r.close()


def _engine_error(served, sql):
    try:
        served.engine.new_session().execute(sql)
    except TiDBTPUError as e:
        return err(getattr(e, "code", 1105), str(e))
    raise AssertionError(f"{sql} did not fail")


# -- (a) the byte stream -------------------------------------------------------

def script_text_native(served, monkeypatch):
    """Text result set, rows encoded a chunk at a time by the native
    encoder (`write_resultset`'s chunk path)."""
    from tidb_tpu import native
    if native.encoder() != "native":
        pytest.skip("the native row encoder did not build here")
    return [(b"\x03SELECT k, v, d, f, dt FROM wb ORDER BY k",
             [lenenc(5)] + WB_COLS + [eof()]
             + [text_row("1", "one", "1.50", "0.5", "2024-01-02"),
                text_row("2", None, None, None, None),
                text_row("3", "three", "-3.25", "3.0", "1999-12-31"),
                eof()])]


def script_text_python(served, monkeypatch):
    """Text result set through `_write_rows_python` (a SHOW statement's
    rows have no chunks), NULL cells among them."""
    return [(b"\x03SHOW COLUMNS FROM wb",
             [lenenc(5)] + SHOW_COLS + [eof()] + SHOW_ROWS + [eof()])]


def script_binary(served, monkeypatch):
    """Binary result sets of a prepared point read: a full row, a row of
    NULLs, no row."""
    head = [lenenc(5)] + WB_COLS + [eof()]
    return [(b"\x16SELECT k, v, d, f, dt FROM wb WHERE k = ?", None),
            (execute_cmd(1, 1), head + [binary_row(*ROWS[0]), eof()]),
            (execute_cmd(1, 2), head + [binary_row(*ROWS[1]), eof()]),
            (execute_cmd(1, 4), head + [eof()])]


def script_ok(served, monkeypatch):
    return [(b"\x03INSERT INTO wlog VALUES (1), (2), (3)", [ok(3)]),
            (b"\x03DELETE FROM wlog WHERE n < 3", [ok(2)])]


def script_err(served, monkeypatch):
    sql = "SELECT nothing FROM no_such_table"
    return [(b"\x03" + sql.encode(), [_engine_error(served, sql)]),
            (b"\x17" + struct.pack("<IBI", 77, 0, 1),
             [err(1243, "Unknown prepared statement handler (77) given "
                        "to EXECUTE")]),
            (b"\x63", [err(1047, "unknown command 99", b"08S01")])]


def script_err_after_rows(served, monkeypatch):
    """A handler that raises after part of a result set was framed: the
    ERR packet follows what was framed, under the next sequence number."""
    plain = srv_mod._text_value

    def failing(v):
        if v == "decimal(10,2)":
            raise TiDBTPUError("the third row cannot be encoded")
        return plain(v)

    monkeypatch.setattr(srv_mod, "_text_value", failing)
    e = TiDBTPUError("the third row cannot be encoded")
    return [(b"\x03SHOW COLUMNS FROM wb",
             [lenenc(5)] + SHOW_COLS + [eof()] + SHOW_ROWS[:2]
             + [err(getattr(e, "code", 1105), str(e))])]


def script_multi_statement(served, monkeypatch):
    return [(b"\x03INSERT INTO wlog VALUES (9); SELECT 1; "
             b"SHOW COLUMNS FROM wb",
             [ok(1, MORE),
              lenenc(1), coldef("1", BIGINT, not_null=True), eof(),
              text_row("1"), eof(MORE)]
             + [lenenc(5)] + SHOW_COLS + [eof()] + SHOW_ROWS + [eof()])]


def script_prepare(served, monkeypatch):
    """COM_STMT_PREPARE's response with parameters and columns, then
    without either; CLOSE and SEND_LONG_DATA answer nothing, and the
    command after them is answered."""
    prep_ok = b"\x00" + struct.pack("<IHH", 1, 2, 2) + b"\x00\x00\x00"
    return [(b"\x16SELECT k, v FROM wb WHERE k > ? AND v <> ?",
             [prep_ok, coldef("?0", VARCHAR), coldef("?1", VARCHAR), eof(),
              coldef("k", BIGINT, not_null=True), coldef("v", VARCHAR),
              eof()]),
            (b"\x16DELETE FROM wlog",
             [b"\x00" + struct.pack("<IHH", 2, 0, 0) + b"\x00\x00\x00"]),
            (b"\x18" + struct.pack("<IH", 1, 1) + b"long", []),
            (b"\x19" + struct.pack("<I", 2), []),
            (b"\x1a" + struct.pack("<I", 1), [ok()])]


def script_ping(served, monkeypatch):
    return [(b"\x0e", [ok()]), (b"\x02wb", [ok()]), (b"\x04wb\x00", [eof()])]


SCRIPTS = [script_text_native, script_text_python, script_binary, script_ok,
           script_err, script_err_after_rows, script_multi_statement,
           script_prepare, script_ping]


@pytest.mark.parametrize("script", SCRIPTS,
                         ids=[s.__name__[7:] for s in SCRIPTS])
def test_a_response_is_byte_for_byte_what_a_plain_framer_builds(
        served, raw, monkeypatch, script):
    for command, packets in script(served, monkeypatch):
        raw.command(command)
        if packets is None:             # a response this case leaves unread
            raw.sock.settimeout(10)
            first = raw._packet()
            assert first[0] == 0x00
            n_cols, n_params = struct.unpack("<HH", first[5:9])
            for _ in range(n_cols + bool(n_cols) + n_params
                           + bool(n_params)):
                raw._packet()
        else:
            raw.expect(packets)
    # and nothing else was on the wire: the next answer follows at once
    raw.command(b"\x0e")
    raw.expect([ok()])


# -- (b) the socket calls ------------------------------------------------------

class Counting:
    """A socket that counts the calls which touch the wire: a send when it
    is made, a recv when it returns bytes (so a server thread parked in
    the recv of the NEXT command is not counted yet)."""

    def __init__(self, sock):
        self._sock = sock
        self.sends = []
        self.recvs = 0

    def sendall(self, data):
        self.sends.append(len(data))
        return self._sock.sendall(data)

    def recv(self, n):
        part = self._sock.recv(n)
        if part:
            self.recvs += 1
        return part

    def __getattr__(self, name):
        return getattr(self._sock, name)


@pytest.fixture
def counted(served, monkeypatch):
    """→ (client, the client's counting socket, the server's): a Client
    whose two ends are wrapped."""
    ends = []
    conn_cls = srv_mod._Conn

    def conn(sock, *args):
        ends.append(Counting(sock))
        return conn_cls(ends[-1], *args)

    create_connection = socket.create_connection

    def connect(*args, **kw):
        return Counting(create_connection(*args, **kw))

    with monkeypatch.context() as m:
        m.setattr(srv_mod, "_Conn", conn)
        m.setattr(socket, "create_connection", connect)
        c = Client(port=served.port)
    yield c, c.sock, ends[0]
    c.close()


def test_a_point_execute_is_one_write_and_one_read_a_side(counted):
    client, csock, ssock = counted
    stmt = client.prepare("SELECT k, v, d, f, dt FROM wb WHERE k = ?")
    assert client.execute_prepared(stmt, [3]) == [
        (3, "three", "-3.25", 3.0, "1999-12-31")]
    for key in (1, 2, 3, 1):
        before = (len(csock.sends), csock.recvs, len(ssock.sends),
                  ssock.recvs)
        rows = client.execute_prepared(stmt, [key])
        assert rows[0][0] == key
        c_send, c_recv, s_send, s_recv = (
            now - was for now, was in zip(
                (len(csock.sends), csock.recvs, len(ssock.sends),
                 ssock.recvs), before))
        assert (c_send, s_send) == (1, 1)
        assert 1 <= c_recv <= 2 and 1 <= s_recv <= 2


def test_the_counters_read_seven_packets_a_send_on_a_point_read(served):
    def read(name, kind):
        return REGISTRY.counters.get((name, (("kind", kind),)), 0)

    names = ("tidb_tpu_wire_socket_calls_total", "tidb_tpu_wire_packets_total")
    with Client(port=served.port) as c:
        stmt = c.prepare("SELECT k, v, d FROM wb WHERE k = ?")
        c.execute_prepared(stmt, [1])
        # the counters are the process's: a connection an earlier test
        # closed counts its COM_QUIT when its thread reads it, so give
        # the threads that serve other connections a moment to end (one
        # that another module left parked in a read counts nothing)
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline and sum(
                "process_request_thread" in t.name
                for t in threading.enumerate()) > 1:
            time.sleep(0.01)
        before = {(n, k): read(n, k) for n in names
                  for k in ("send", "recv")}
        for i in range(20):
            assert c.execute_prepared(stmt, [1 + i % 3])[0][0] == 1 + i % 3
        moved = {key: read(*key) - was for key, was in before.items()}
    calls, packets = names
    # count, three definitions, EOF, row, EOF in ONE send; one command
    # packet a burst
    assert moved[calls, "send"] == 20
    assert moved[packets, "send"] == 140
    assert moved[packets, "send"] / moved[calls, "send"] >= 7
    assert 20 <= moved[calls, "recv"] <= 40
    assert moved[packets, "recv"] == 20


def test_the_new_counters_keep_the_naming_contract():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_metrics", os.path.join(root, "tools", "check_metrics.py"))
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)
    path = os.path.join(root, "tidb_tpu", "server", "__init__.py")
    assert cm.check_file(path) == []
    src = open(path).read()
    for name in ("tidb_tpu_wire_socket_calls_total",
                 "tidb_tpu_wire_packets_total"):
        assert f'"{name}"' in src           # the file checked holds them


def test_the_wire_write_span_carries_sends_and_packets(served):
    from tidb_tpu.util import timeline
    with Client(port=served.port) as c:
        stmt = c.prepare("SELECT k, v, d FROM wb WHERE k = ?")
        with timeline.capture() as events:
            c.execute_prepared(stmt, [2])
            c.query("SELECT k FROM wb ORDER BY k")
            # a span is recorded when it ends, after the send: the answer
            # to a third command says the second's is there
            c.ping()
    events = events.events
    writes = [e for e in events if e["name"] == "wire.write"]
    assert [(e["cat"], e["args"]["sends"], e["args"]["packets"])
            for e in writes] == [("wire", 1, 7), ("wire", 1, 7)]
    roots = {e["args"]["id"] for e in events if e["name"] == "stmt"}
    assert all(e["args"]["parent"] in roots for e in writes)


# -- (c) a large result set streams --------------------------------------------

@pytest.mark.parametrize("sql,path", [
    ("SELECT n, pad FROM big", "native"),
    ("SELECT n, pad FROM big", "python"),
], ids=["native-chunks", "python-rows"])
def test_a_large_result_set_leaves_in_pieces_before_its_end(
        served, monkeypatch, sql, path):
    """Several times BUFFER_BYTES of rows: the buffer goes out whenever it
    passes the constant, so the client holds most of the rows before the
    final EOF is framed and the connection never holds more than the
    constant plus one piece."""
    seen = {"peak": 0, "largest": 0, "sends_at_eof": []}

    class Watched(srv_mod._Conn):
        def _queue(self, framed, packets):
            seen["largest"] = max(seen["largest"], len(framed))
            super()._queue(framed, packets)

        def _send(self, data, packets):
            if isinstance(data, bytearray):         # the buffer, flushed
                seen["peak"] = max(seen["peak"], len(data))
            super()._send(data, packets)

        def write_eof(self, status=0x0002):
            seen["sends_at_eof"].append(self.sends)
            super().write_eof(status)

    monkeypatch.setattr(srv_mod, "_Conn", Watched)
    if path == "python":
        from tidb_tpu import native
        monkeypatch.setattr(native, "encode_text_rows",
                            lambda *a, **k: None)
    with Client(port=served.port) as c:
        seen["sends_at_eof"].clear()
        _names, rows = c.query(sql)
    assert len(rows) == 12_000 and rows[11_999][0] == "11999"
    total = sum(len(text_row(*r)) + 4 for r in rows)
    assert total > 8 * BUFFER_BYTES
    after_defs, at_final_eof = seen["sends_at_eof"][-2:]
    pieces = at_final_eof - after_defs
    assert seen["peak"] < BUFFER_BYTES + seen["largest"]
    if path == "python":
        assert seen["largest"] < 100        # row packets, one at a time
        assert pieces >= total // (2 * BUFFER_BYTES) >= 4
    else:
        # what came before it, then the chunk itself, uncopied
        assert pieces >= 2 and seen["largest"] >= BUFFER_BYTES
        assert seen["peak"] < BUFFER_BYTES


# -- the edges -----------------------------------------------------------------

@pytest.fixture(scope="module")
def tls_served(tmp_path_factory):
    d = tmp_path_factory.mktemp("tls")
    cert, key = str(d / "c.pem"), str(d / "k.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "1", "-subj",
         "/CN=localhost"], check=True, capture_output=True)
    eng = Engine()
    eng.new_session().execute("CREATE TABLE t (a BIGINT)")
    eng.new_session().execute("INSERT INTO t VALUES (42)")
    server = Server(eng, port=0, ssl_cert=cert, ssl_key=key).start()
    yield server
    server.stop()


def test_a_tls_hello_sent_with_the_ssl_request_is_not_swallowed(tls_served):
    """A client that does not wait between its SSLRequest packet and the
    TLS hello: both arrive in one burst, and a server reading ahead would
    take the hello's bytes for packets."""
    sock = socket.create_connection(("127.0.0.1", tls_served.port),
                                    timeout=10)
    h = b""
    while len(h) < 4:
        h += sock.recv(4 - len(h))
    n = int.from_bytes(h[:3], "little")
    greeting = b""
    while len(greeting) < n:
        greeting += sock.recv(n - len(greeting))
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE
    incoming, outgoing = ssl.MemoryBIO(), ssl.MemoryBIO()
    tls = ctx.wrap_bio(incoming, outgoing)
    with pytest.raises(ssl.SSLWantReadError):
        tls.do_handshake()
    caps = 0x0200 | 0x8000 | 0x1 | 0x800
    ssl_request = struct.pack("<II", caps, 1 << 24) + b"\xff" + b"\x00" * 23
    sock.sendall(frame([ssl_request]) + outgoing.read())      # ONE write

    def pump(call, *args):
        while True:
            try:
                out = call(*args)
            except ssl.SSLWantReadError:
                out = None
            if outgoing.pending:
                sock.sendall(outgoing.read())
            if out is not None:
                return out
            data = sock.recv(65536)
            assert data, "server closed during TLS"
            incoming.write(data)

    pump(lambda: tls.do_handshake() or True)
    pump(tls.write, frame([ssl_request + b"root\x00\x00"], seq=2))
    answer = b""
    while len(answer) < 11:
        answer += pump(tls.read, 11 - len(answer))
    assert answer == frame([ok()], seq=3)
    pump(tls.write, frame([b"\x03SELECT a FROM t"], seq=0))
    want = frame([lenenc(1), coldef("a", BIGINT), eof(), text_row("42"),
                  eof()])
    got = b""
    while len(got) < len(want):
        got += pump(tls.read, len(want) - len(got))
    assert got == want
    sock.close()


def _conn_id(c):
    return int(c.query("SELECT CONNECTION_ID()")[1][0][0])


def test_kill_of_an_idle_connection_and_kill_beside_a_response(served):
    """KILL closes the socket AFTER the current command's response is on
    the wire: the killer reads its OK even when it kills itself, and an
    idle victim finds its connection dead at its next command."""
    with Client(port=served.port) as admin:
        victim = Client(port=served.port, auto_reconnect=False)
        assert admin.execute(f"KILL {_conn_id(victim)}") == 0
        with pytest.raises((ClientError, OSError)):
            victim.query("SELECT 1")
            victim.query("SELECT 1")
        victim.sock.close()
        own = Client(port=served.port, auto_reconnect=False)
        names, rows = own.query(f"KILL {_conn_id(own)}")
        assert (names, rows) == ([], [])            # its OK arrived
        with pytest.raises((ClientError, OSError)):
            own.query("SELECT 1")
            own.query("SELECT 1")
        own.sock.close()
        assert admin.query("SELECT 1")[1] == [("1",)]


def test_commands_without_a_response_leave_nothing_behind(served):
    with Client(port=served.port) as c:
        a = c.prepare("SELECT k FROM wb WHERE k = ?")
        b = c.prepare("SELECT v FROM wb WHERE k = ?")
        c.seq = 0
        c._write_packet(b"\x18" + struct.pack("<IH", a.stmt_id, 0) + b"2")
        c.close_prepared(b)
        # the next commands are answered, in order, by their own responses
        assert c.execute_prepared(a, ["ignored"]) == [(2,)]
        assert c.query("SELECT 5")[1] == [("5",)]
        with pytest.raises(ClientError, match="1243"):
            c.execute_prepared(b, [1])


def test_a_reconnected_client_reads_nothing_of_the_dead_socket(monkeypatch):
    """The first connection dies with part of a packet in the client's
    read buffer; the retry on the second connection must read that
    connection's answer from its first byte."""
    greeting = (b"\x0a" + b"fake\x00" + struct.pack("<I", 1) + b"12345678\x00"
                + struct.pack("<H", 0xF7FF) + b"\xff" + struct.pack("<H", 2)
                + struct.pack("<H", 0x0000) + b"\x15" + b"\x00" * 10
                + b"123456789012\x00" + b"mysql_native_password\x00")
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(2)
    # an OK whose header announces more than ever arrives
    stale = (40).to_bytes(3, "little") + b"\x01" + b"\x00\x63"
    served_sql = []

    def packet(s):
        h = b""
        while len(h) < 4:
            h += s.recv(4 - len(h))
        body = b""
        while len(body) < int.from_bytes(h[:3], "little"):
            body += s.recv(4096)
        return body

    def serve(affected, tail):
        s, _ = listener.accept()
        with s:
            s.sendall(frame([greeting], seq=0))
            packet(s)
            s.sendall(frame([ok()], seq=2))
            served_sql.append(packet(s)[1:])
            s.sendall(frame([ok(affected)]) + tail)
            if not tail:
                served_sql.append(packet(s)[1:])
                s.sendall(frame([ok(affected + 1)]))
                packet(s)                       # COM_QUIT

    def fake_server():
        serve(11, stale)
        serve(21, b"")

    t = threading.Thread(target=fake_server, daemon=True)
    t.start()
    monkeypatch.setattr(Client, "RECONNECT_ATTEMPTS", 2)
    c = Client(port=listener.getsockname()[1], timeout=10)
    assert c.execute("SELECT 'first'") == 11
    # the dead connection's half packet is in the reader; the statement is
    # read-only, so the client reconnects and sends it again
    assert c.execute("SELECT 'second'") == 21
    assert c.execute("SELECT 'third'") == 22
    c.close()
    t.join(10)
    assert not t.is_alive()
    listener.close()
    assert served_sql == [b"SELECT 'first'", b"SELECT 'second'",
                          b"SELECT 'third'"]
