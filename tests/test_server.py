"""MySQL wire protocol: in-repo client against the MOServer
(reference analogue: frontend protocol tests + clients/python)."""

import pytest

from matrixone_tpu import client
from matrixone_tpu.frontend.server import MOServer


@pytest.fixture(scope="module")
def server():
    srv = MOServer(port=0).start()   # ephemeral port
    yield srv
    srv.stop()


def test_connect_ping_query(server):
    c = client.connect(port=server.port)
    assert c.ping()
    cols, rows = c.query("select 1 + 1 as s")
    assert cols == ["s"] and rows == [("2",)]
    c.close()


def test_ddl_dml_roundtrip(server):
    c = client.connect(port=server.port)
    c.execute("create table wt (id bigint, name varchar(20), p decimal(8,2))")
    n = c.execute("insert into wt values (1, 'ann', 1.50), (2, null, 2.25)")
    assert n == 2
    cols, rows = c.query("select id, name, p from wt order by id")
    assert cols == ["id", "name", "p"]
    assert rows == [("1", "ann", "1.5"), ("2", None, "2.25")]
    assert c.execute("update wt set p = 9.99 where id = 1") == 1
    _, rows = c.query("select p from wt where id = 1")
    assert rows == [("9.99",)]
    c.close()


@pytest.mark.parametrize("x, y", [
    ("266581.321", "266581.321"),      # product's scaled int > 2^53
    ("-300000.001", "300239.977"),
    ("0.001", "0.001"), ("2.500", "5.000"), ("1.000", "7.000")])
def test_decimal_is_exact_on_the_wire(server, x, y):
    """A DECIMAL goes out digit for digit from its scaled integer: a
    detour through float loses digits past 2^53 (an SF1 TPC-H sum), and
    small values keep the shape they always had (trailing zeros dropped,
    one fractional digit kept)."""
    from decimal import Decimal
    want = Decimal(x) * Decimal(y)
    text = format(want, "f").rstrip("0")
    text += "0" if text.endswith(".") else ""
    c = client.connect(port=server.port)
    c.execute("create table if not exists bigdec (id bigint primary key"
              " auto_increment, x decimal(18,3), y decimal(18,3))")
    c.execute(f"insert into bigdec (x, y) values ({x}, {y})")
    _, rows = c.query("select x * y from bigdec order by id desc limit 1")
    assert rows == [(text,)] and Decimal(rows[0][0]) == want
    stmt = c.prepare("select x * y from bigdec where id > ? order by id"
                     " desc limit 1")
    _, rows, _ = stmt.execute(0)
    assert rows == [(text,)]
    c.close()


def test_error_packet(server):
    c = client.connect(port=server.port)
    with pytest.raises(client.MySQLError, match="no such table"):
        c.query("select * from does_not_exist")
    # connection still usable after an error
    assert c.ping()
    c.close()


def test_concurrent_connections_share_engine(server):
    c1 = client.connect(port=server.port)
    c2 = client.connect(port=server.port)
    c1.execute("create table shared (x bigint)")
    c1.execute("insert into shared values (42)")
    _, rows = c2.query("select x from shared")
    assert rows == [("42",)]
    # txn isolation across connections
    c1.execute("begin")
    c1.execute("insert into shared values (43)")
    _, rows = c2.query("select count(*) from shared")
    assert rows == [("1",)]
    c1.execute("commit")
    _, rows = c2.query("select count(*) from shared")
    assert rows == [("2",)]
    c1.close()
    c2.close()


def test_auth_rejects_bad_password():
    """ADVICE r1 medium: credentials must actually be verified
    (reference: frontend/authenticate.go mysql_native_password)."""
    srv = MOServer(port=0, users={"root": "s3cret"}).start()
    try:
        with pytest.raises(client.MySQLError, match="Access denied"):
            client.connect(port=srv.port, user="root", password="wrong")
        with pytest.raises(client.MySQLError, match="Access denied"):
            client.connect(port=srv.port, user="nobody", password="s3cret")
        c = client.connect(port=srv.port, user="root", password="s3cret")
        assert c.ping()
        c.close()
    finally:
        srv.stop()


def test_auth_empty_password_default():
    srv = MOServer(port=0).start()          # default users={"root": ""}
    try:
        c = client.connect(port=srv.port, user="root", password="")
        assert c.ping()
        c.close()
        with pytest.raises(client.MySQLError, match="Access denied"):
            client.connect(port=srv.port, user="root", password="x")
    finally:
        srv.stop()


def test_prepared_statement_roundtrip(server):
    """COM_STMT_PREPARE / EXECUTE binary protocol
    (reference: mysql_cmd_executor.go:4348 wire prepared statements)."""
    c = client.connect(port=server.port)
    c.execute("create table ps (id bigint, name varchar(20), w double)")
    ins = c.prepare("insert into ps values (?, ?, ?)")
    assert ins.n_params == 3
    ins.execute(1, "ann", 1.5)
    ins.execute(2, "bob", 2.25)
    ins.execute(3, None, None)
    sel = c.prepare("select name, w from ps where id >= ? order by id")
    names, rows, _ = sel.execute(2)
    assert names == ["name", "w"]
    assert rows == [("bob", "2.25"), (None, None)]
    # re-execute with different params (type rebind)
    _, rows, _ = sel.execute(1)
    assert len(rows) == 3
    ins.close()
    sel.close()
    c.close()


def test_multipacket_payload(server):
    """ADVICE r1 low: >16MB payloads span packets and must reassemble."""
    c = client.connect(port=server.port)
    big = "x" * (17 * 1024 * 1024)
    cols, rows = c.query(f"select length('{big}') as n")
    assert rows == [(str(len(big)),)]
    c.close()
