"""`tidb_tpu.client.Client.prepare` / `execute_prepared`: COM_STMT_PREPARE and
COM_STMT_EXECUTE with binary parameters and binary result rows, round trips
against the wire server (whose side `tests/test_server_stmt.py` pins with a
hand-rolled client)."""

import datetime
import decimal
import socket

import pytest

from tidb_tpu.client import Client, ClientError
from tidb_tpu.server import Server
from tidb_tpu.session import Engine

ROWS = [(1, "one", 1.5, "2024-01-15", "10.25", "2024-01-15 10:20:30"),
        (2, "two", None, "2024-02-20", "20.50", None),
        (3, None, 3.5, None, None, "1999-12-31 23:59:59"),
        (4, "it's", -0.25, "1992-01-01", "-0.05", "2000-01-01 00:00:00")]


@pytest.fixture(scope="module")
def srv():
    eng = Engine()
    server = Server(eng, port=0).start()
    s = eng.new_session()
    s.execute("CREATE TABLE ps (a BIGINT PRIMARY KEY, b VARCHAR(16), "
              "c DOUBLE, d DATE, e DECIMAL(15,2), f DATETIME)")
    s.execute("INSERT INTO ps VALUES (1,'one',1.5,'2024-01-15',10.25,"
              "'2024-01-15 10:20:30'),(2,'two',NULL,'2024-02-20',20.50,NULL),"
              "(3,NULL,3.5,NULL,NULL,'1999-12-31 23:59:59'),"
              "(4,'it''s',-0.25,'1992-01-01',-0.05,'2000-01-01 00:00:00')")
    yield server
    server.stop()


@pytest.fixture
def cli(srv):
    with Client(port=srv.port, auto_reconnect=False) as c:
        yield c


# one parameter of each type the issue names, bound to the column of its
# type: (predicate, parameter, the keys it selects)
PARAMETERS = [
    ("a = ?", 3, [3]),                                      # BIGINT
    ("a = ?", -7, []),
    ("a > ?", True, [2, 3, 4]),                             # TINY (bool)
    ("e = ?", decimal.Decimal("20.50"), [2]),               # DECIMAL(15,2)
    ("e < ?", decimal.Decimal("0"), [4]),
    ("d = ?", datetime.date(2024, 1, 15), [1]),             # DATE
    ("d < ?", "2000-01-01", [4]),                           # DATE as text
    ("f = ?", datetime.datetime(1999, 12, 31, 23, 59, 59), [3]),
    ("b = ?", "two", [2]),                                  # VARCHAR
    ("b = ?", "it's", [4]),                                 # quoted in text
    ("b = ?", b"one", [1]),                                 # bytes
    ("c > ?", 1.0, [1, 3]),                                 # DOUBLE
    ("b = ?", None, []),                                    # NULL: no row
    ("a = ? OR ? IS NULL", (9, None), [1, 2, 3, 4]),        # two, one NULL
]


@pytest.mark.parametrize("predicate,param,keys", PARAMETERS,
                         ids=[f"{p[0]}-{p[1]!r}" for p in PARAMETERS])
def test_a_bound_parameter_selects_what_the_literal_would(cli, predicate,
                                                          param, keys):
    params = list(param) if isinstance(param, tuple) else [param]
    stmt = cli.prepare(f"SELECT a FROM ps WHERE {predicate} ORDER BY a")
    assert stmt.n_params == len(params) and stmt.names == ["a"]
    assert cli.execute_prepared(stmt, params) == [(k,) for k in keys]


def test_every_column_type_and_null_cell_comes_back(cli):
    stmt = cli.prepare("SELECT a, b, c, d, e, f FROM ps WHERE a >= ? "
                       "ORDER BY a")
    rows = cli.execute_prepared(stmt, [1])
    assert rows == ROWS                 # several rows, NULL cells as None
    assert stmt.names == ["a", "b", "c", "d", "e", "f"]
    # integers are int and doubles float; the rest as `query` gives them
    assert [type(v) for v in rows[0]] == [int, str, float, str, str, str]
    _names, text = cli.query("SELECT d, e, f FROM ps ORDER BY a")
    assert [r[3:] for r in rows] == text


def test_zero_rows_and_a_statement_without_a_result_set(cli):
    stmt = cli.prepare("SELECT a, b FROM ps WHERE a = ?")
    assert cli.execute_prepared(stmt, [99]) == []
    cli.execute("CREATE TABLE IF NOT EXISTS ps_w (k BIGINT, v VARCHAR(8))")
    ins = cli.prepare("INSERT INTO ps_w VALUES (?, ?), (?, ?)")
    assert ins.n_params == 4 and ins.names == []
    assert cli.execute_prepared(ins, [1, "x", 2, None]) == []
    assert cli.affected_rows == 2
    assert cli.query("SELECT k, v FROM ps_w ORDER BY k")[1] == \
        [("1", "x"), ("2", None)]
    cli.execute("DROP TABLE ps_w")


def test_an_unknown_handle_and_a_wrong_parameter_count(cli):
    stmt = cli.prepare("SELECT a FROM ps WHERE a = ?")
    with pytest.raises(ClientError, match="1 parameter"):
        cli.execute_prepared(stmt, [])
    cli.close_prepared(stmt)
    with pytest.raises(ClientError) as e:
        cli.execute_prepared(stmt, [1])
    assert e.value.code == 1243
    # the connection is still in step after the error
    assert cli.query("SELECT 1")[1] == [("1",)]


def test_a_handle_lives_on_its_own_connection(srv, cli):
    stmt = cli.prepare("SELECT a FROM ps WHERE a = ?")
    with Client(port=srv.port, auto_reconnect=False) as other:
        with pytest.raises(ClientError) as e:
            other.execute_prepared(stmt, [1])
        assert e.value.code == 1243
    assert cli.execute_prepared(stmt, [1]) == [(1,)]


def test_re_execute_after_other_statements_on_the_connection(cli):
    by_key = cli.prepare("SELECT b FROM ps WHERE a = ?")
    by_name = cli.prepare("SELECT a FROM ps WHERE b = ?")
    assert by_key.stmt_id != by_name.stmt_id
    for _ in range(3):
        assert cli.execute_prepared(by_key, [2]) == [("two",)]
        assert cli.query("SELECT COUNT(*) FROM ps")[1] == [("4",)]
        assert cli.execute_prepared(by_name, ["one"]) == [(1,)]
        assert cli.execute_prepared(by_key, [3]) == [(None,)]
    with pytest.raises(ClientError):
        cli.query("SELECT nothing FROM nowhere")
    assert cli.execute_prepared(by_key, [4]) == [("it's",)]


def test_a_syntax_error_at_prepare_time_is_raised_at_execute(cli):
    # the server's PREPARE is best effort about metadata; the statement's
    # error comes when it runs
    stmt = cli.prepare("SELECT a FROM no_such_table WHERE a = ?")
    with pytest.raises(ClientError):
        cli.execute_prepared(stmt, [1])
    assert cli.query("SELECT 2")[1] == [("2",)]


def test_both_ends_of_a_connection_send_at_once(srv):
    """TCP_NODELAY on the client's socket and on the server's accepted one:
    a result set leaves in several small writes, and Nagle's algorithm
    against the peer's delayed ACK would hold each statement 40 ms."""
    with Client(port=srv.port) as c:
        assert c.sock.getsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY)
        import gc
        served = [o for o in gc.get_objects()
                  if isinstance(o, socket.socket) and o.fileno() != -1
                  and o.type == socket.SOCK_STREAM
                  and _peer(o) == c.sock.getsockname()]
        assert len(served) == 1
        assert served[0].getsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY)


def _peer(sock):
    try:
        return sock.getpeername()
    except OSError:
        return None
