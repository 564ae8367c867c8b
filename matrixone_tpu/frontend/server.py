"""MySQL wire-protocol server (reference: pkg/frontend MOServer,
server.go:611/:99/:329 + codec + authenticate.go — redesigned to the
protocol surface real clients need: handshake v10 with a random nonce,
mysql_native_password verification against configured users,
COM_QUERY/COM_PING/COM_INIT_DB/COM_QUIT text protocol, and the
COM_STMT_PREPARE / COM_STMT_EXECUTE / COM_STMT_CLOSE / COM_STMT_RESET
binary prepared-statement protocol (reference:
frontend/mysql_cmd_executor.go:4348 handlePrepareStmt wire path).

Auth model: `users` maps username -> plaintext password; the server stores
only SHA1(SHA1(password)) (stage2, what MySQL's mysql.user holds) and
verifies the client's 20-byte scramble against a per-connection random
nonce. Accept-all requires an explicit ``insecure=True``.

Real MySQL clients can connect on the configured port;
matrixone_tpu.client is the in-repo SDK speaking the same protocol.
"""

from __future__ import annotations

import hashlib
import secrets
import socket
import struct
import threading
from typing import Dict, Optional

from matrixone_tpu.container.dtypes import DType, TypeOid
from matrixone_tpu.utils.lifecycle import ServiceThreads
from matrixone_tpu.frontend.session import Result, Session

# MySQL protocol constants
_CAP_PROTOCOL_41 = 0x0200
_CAP_PLUGIN_AUTH = 0x80000
_CAP_SECURE_CONN = 0x8000
_CAP_CONNECT_WITH_DB = 0x8
_CAP_PLUGIN_AUTH_LENENC = 0x200000
_CAPS = 0xF7FF | _CAP_PLUGIN_AUTH | _CAP_SECURE_CONN | _CAP_PLUGIN_AUTH_LENENC

_COM_QUIT = 0x01
_COM_INIT_DB = 0x02
_COM_QUERY = 0x03
_COM_PING = 0x0E
_COM_STMT_PREPARE = 0x16
_COM_STMT_EXECUTE = 0x17
_COM_STMT_CLOSE = 0x19
_COM_STMT_RESET = 0x1A

_MYSQL_TYPE = {
    TypeOid.BOOL: 1, TypeOid.INT8: 1, TypeOid.INT16: 2, TypeOid.INT32: 3,
    TypeOid.INT64: 8, TypeOid.UINT8: 1, TypeOid.UINT16: 2,
    TypeOid.UINT32: 3, TypeOid.UINT64: 8, TypeOid.FLOAT32: 4,
    TypeOid.FLOAT64: 5, TypeOid.DECIMAL64: 246, TypeOid.DATE: 10,
    TypeOid.DATETIME: 12, TypeOid.TIMESTAMP: 7,
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


def _read_lenenc(data: bytes, pos: int):
    b0 = data[pos]
    if b0 < 0xFB:
        return b0, pos + 1
    if b0 == 0xFB:            # NULL marker (only in row data)
        return None, pos + 1
    if b0 == 0xFC:
        return int.from_bytes(data[pos + 1:pos + 3], "little"), pos + 3
    if b0 == 0xFD:
        return int.from_bytes(data[pos + 1:pos + 4], "little"), pos + 4
    return int.from_bytes(data[pos + 1:pos + 9], "little"), pos + 9


# client-side scramble lives in the thin SDK (stdlib-only); re-exported
# here for protocol-level tests
from matrixone_tpu.client import native_password_scramble  # noqa: E402,F401


def password_stage2(password: str) -> bytes:
    """What the server persists: SHA1(SHA1(password)) (mysql.user style)."""
    return hashlib.sha1(hashlib.sha1(password.encode()).digest()).digest()


def verify_native_password(stage2: bytes, nonce: bytes,
                           auth_response: bytes) -> bool:
    """Server side: recover SHA1(pw) = response XOR SHA1(nonce+stage2) and
    check SHA1(recovered) == stage2 (reference: frontend/authenticate.go
    checkPassword)."""
    if not stage2:                      # empty password account
        return auth_response == b""
    if len(auth_response) != 20:
        return False
    mix = hashlib.sha1(nonce + stage2).digest()
    recovered = bytes(a ^ b for a, b in zip(auth_response, mix))
    return hashlib.sha1(recovered).digest() == stage2


def _count_params(node) -> int:
    """Number of ? placeholders in a parsed statement (max index + 1)."""
    import dataclasses as dc
    from matrixone_tpu.sql import ast
    best = 0
    if isinstance(node, ast.Param):
        return node.index + 1
    if dc.is_dataclass(node) and isinstance(node, ast.Node):
        for f in dc.fields(node):
            v = getattr(node, f.name)
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(x, ast.Node):
                    best = max(best, _count_params(x))
                elif isinstance(x, (list, tuple)):
                    for y in x:
                        if isinstance(y, ast.Node):
                            best = max(best, _count_params(y))
    return best


class _PreparedStmt:
    def __init__(self, stmt_id: int, sql: str, n_params: int):
        self.stmt_id = stmt_id
        self.sql = sql
        self.n_params = n_params
        self.param_types: Optional[list] = None   # sticky across executes


def _decode_binary_params(body: bytes, pos: int, stmt: _PreparedStmt) -> list:
    """Decode COM_STMT_EXECUTE parameter values (binary protocol)."""
    n = stmt.n_params
    nullmap = body[pos:pos + (n + 7) // 8]
    pos += (n + 7) // 8
    new_bound = body[pos]
    pos += 1
    if new_bound:
        stmt.param_types = [
            (body[pos + 2 * i], body[pos + 2 * i + 1]) for i in range(n)]
        pos += 2 * n
    if stmt.param_types is None:
        raise ValueError("COM_STMT_EXECUTE without bound parameter types")
    params = []
    for i, (ptype, flags) in enumerate(stmt.param_types):
        if nullmap[i // 8] & (1 << (i % 8)):
            params.append(None)
            continue
        unsigned = bool(flags & 0x80)
        if ptype in (1, 2, 3, 8, 9, 13):   # tiny/short/long/longlong/year
            width = {1: 1, 2: 2, 3: 4, 8: 8, 9: 4, 13: 2}[ptype]
            params.append(int.from_bytes(body[pos:pos + width], "little",
                                         signed=not unsigned))
            pos += width
        elif ptype == 4:                          # float
            params.append(struct.unpack("<f", body[pos:pos + 4])[0])
            pos += 4
        elif ptype == 5:                          # double
            params.append(struct.unpack("<d", body[pos:pos + 8])[0])
            pos += 8
        elif ptype == 6:                          # NULL type
            params.append(None)
        elif ptype in (10, 12, 7):                # date / datetime / timestamp
            ln = body[pos]
            pos += 1
            raw = body[pos:pos + ln]
            pos += ln
            import datetime
            if ln == 0:
                params.append(datetime.date(1970, 1, 1))
            else:
                y, m, d = struct.unpack("<HBB", raw[:4])
                if ptype == 10 or ln == 4:
                    params.append(datetime.date(y, m, d))
                else:
                    hh, mm, ss = raw[4:7] if ln >= 7 else (0, 0, 0)
                    params.append(datetime.datetime(y, m, d, hh, mm, ss))
        else:                                     # lenenc string-shaped
            ln, pos = _read_lenenc(body, pos)
            raw = body[pos:pos + (ln or 0)]
            pos += ln or 0
            if ptype == 246:                      # NEWDECIMAL
                params.append(float(raw.decode()))
            else:
                params.append(raw.decode("utf-8", "replace"))
    return params


class _Conn:
    def __init__(self, sock: socket.socket, server: "MOServer"):
        self.sock = sock
        self.server = server
        self.session: Optional[Session] = None
        self.insecure = server.insecure
        self.seq = 0
        self._stmts: Dict[int, _PreparedStmt] = {}
        self._next_stmt = 1

    # ---- packet framing
    def _send(self, payload: bytes):
        while True:
            chunk, payload = payload[:0xFFFFFF], payload[0xFFFFFF:]
            header = struct.pack("<I", len(chunk))[:3] + bytes([self.seq & 0xFF])
            self.sock.sendall(header + chunk)
            self.seq += 1
            if len(chunk) < 0xFFFFFF:
                return

    def _recv(self) -> Optional[bytes]:
        """One logical payload: packets of exactly 0xFFFFFF bytes continue
        into the next packet (sender-side splitting mirrored here)."""
        payload = b""
        while True:
            header = self._recv_n(4)
            if header is None:
                return None
            length = int.from_bytes(header[:3], "little")
            self.seq = header[3] + 1
            part = self._recv_n(length)
            if part is None:
                return None
            payload += part
            if length < 0xFFFFFF:
                return payload

    def _recv_n(self, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            part = self.sock.recv(n - len(buf))
            if not part:
                return None
            buf += part
        return buf

    # ---- packets
    def send_handshake(self) -> bytes:
        self.seq = 0
        # 20-byte random nonce, non-zero bytes (MySQL requirement)
        nonce = bytes(secrets.randbelow(254) + 1 for _ in range(20))
        payload = (bytes([10])
                   + b"8.0.0-matrixone-tpu\x00"
                   + struct.pack("<I", threading.get_ident() & 0xFFFFFFFF)
                   + nonce[:8] + b"\x00"                    # auth data part 1
                   + struct.pack("<H", _CAPS & 0xFFFF)
                   + bytes([0x21])                          # charset utf8
                   + struct.pack("<H", 0x0002)              # status
                   + struct.pack("<H", (_CAPS >> 16) & 0xFFFF)
                   + bytes([21])                            # auth data len
                   + b"\x00" * 10
                   + nonce[8:] + b"\x00"                    # auth data part 2
                   + b"mysql_native_password\x00")
        self._send(payload)
        return nonce

    def authenticate(self, nonce: bytes) -> bool:
        """Parse HandshakeResponse41, verify the scramble, and resolve
        the account context ('account:user' logins select the tenant —
        reference: authenticate.go)."""
        pkt = self._recv()
        if pkt is None:
            return False
        if self.insecure:
            self.session = self.server.make_session(None)
            return True
        try:
            caps = int.from_bytes(pkt[0:4], "little")
            pos = 4 + 4 + 1 + 23          # caps, max packet, charset, filler
            end = pkt.index(b"\x00", pos)
            user = pkt[pos:end].decode("utf-8", "replace")
            pos = end + 1
            if caps & _CAP_PLUGIN_AUTH_LENENC:
                ln, pos = _read_lenenc(pkt, pos)
                auth = pkt[pos:pos + (ln or 0)]
                pos += ln or 0
            elif caps & _CAP_SECURE_CONN:
                ln = pkt[pos]
                pos += 1
                auth = pkt[pos:pos + ln]
                pos += ln
            else:
                end = pkt.index(b"\x00", pos)
                auth = pkt[pos:end]
        except (ValueError, IndexError):
            self.send_err("malformed handshake response", code=1043,
                          state="08S01")
            return False
        resolved = self.server.auth_mgr.resolve_login(user)
        if resolved is None or not verify_native_password(
                resolved[2], nonce, auth):
            self.send_err(f"Access denied for user '{user}'",
                          code=1045, state="28000")
            return False
        account, uname, _stage2 = resolved
        ctx = self.server.auth_mgr.context_for(account, uname)
        self.session = self.server.make_session(ctx)
        return True

    def send_ok(self, affected: int = 0, info: str = ""):
        payload = (b"\x00" + _lenenc_int(affected) + _lenenc_int(0)
                   + struct.pack("<H", 0x0002) + struct.pack("<H", 0)
                   + info.encode())
        self._send(payload)

    def send_err(self, msg: str, code: int = 1105, state: str = "HY000"):
        payload = (b"\xff" + struct.pack("<H", code) + b"#"
                   + state.encode()[:5].ljust(5, b"0") + msg.encode()[:1024])
        self._send(payload)

    def send_eof(self):
        self._send(b"\xfe" + struct.pack("<H", 0) + struct.pack("<H", 0x0002))

    def _send_column_defs(self, result: Result, binary: bool):
        batch = result.batch
        names = result.column_names
        dtypes = [batch.columns[n].dtype for n in names]
        self._send(_lenenc_int(len(names)))
        for name, dtype in zip(names, dtypes):
            # binary rows are sent as lenenc strings, so declare VAR_STRING
            mysql_t = 253 if binary else _MYSQL_TYPE.get(dtype.oid, 253)
            col = (_lenenc_str(b"def") + _lenenc_str(b"") + _lenenc_str(b"")
                   + _lenenc_str(b"") + _lenenc_str(name.encode())
                   + _lenenc_str(name.encode()) + bytes([0x0C])
                   + struct.pack("<H", 0x21) + struct.pack("<I", 1024)
                   + bytes([mysql_t]) + struct.pack("<H", 0)
                   + bytes([dtype.scale & 0xFF]) + b"\x00\x00")
            self._send(col)
        self.send_eof()

    def send_resultset(self, result: Result):
        self._send_column_defs(result, binary=False)
        for row in _wire_rows(result):
            out = b""
            for v in row:
                if v is None:
                    out += b"\xfb"
                else:
                    out += _lenenc_str(str(v).encode())
            self._send(out)
        self.send_eof()

    def send_binary_resultset(self, result: Result):
        """Binary-protocol resultset (COM_STMT_EXECUTE responses). All
        columns are declared VAR_STRING so every value is a lenenc string —
        type fidelity lives in the text; clients coerce by declared type."""
        self._send_column_defs(result, binary=True)
        ncols = len(result.column_names)
        nm_len = (ncols + 2 + 7) // 8
        for row in _wire_rows(result):
            nullmap = bytearray(nm_len)
            body = b""
            for i, v in enumerate(row):
                if v is None:
                    nullmap[(i + 2) // 8] |= 1 << ((i + 2) % 8)
                else:
                    body += _lenenc_str(str(v).encode())
            self._send(b"\x00" + bytes(nullmap) + body)
        self.send_eof()

    def _result_to_packets(self, r: Result, binary: bool):
        if r.batch is not None:
            if binary:
                self.send_binary_resultset(r)
            else:
                self.send_resultset(r)
        elif r.text is not None:
            from matrixone_tpu.container import Batch, dtypes as dt
            b = Batch.from_pydict({"EXPLAIN": r.text.split("\n")},
                                  {"EXPLAIN": dt.TEXT})
            rr = Result(batch=b)
            if binary:
                self.send_binary_resultset(rr)
            else:
                self.send_resultset(rr)
        else:
            self.send_ok(affected=r.affected)

    # ---- prepared statements
    def _handle_prepare(self, sql: str):
        from matrixone_tpu.sql.parser import parse
        stmts = parse(sql)
        if len(stmts) != 1:
            raise ValueError("can only prepare a single statement")
        n_params = _count_params(stmts[0])
        stmt = _PreparedStmt(self._next_stmt, sql, n_params)
        self._next_stmt += 1
        self._stmts[stmt.stmt_id] = stmt
        # COM_STMT_PREPARE_OK: num_columns=0 (defs are sent per-execute)
        self._send(b"\x00" + struct.pack("<I", stmt.stmt_id)
                   + struct.pack("<H", 0) + struct.pack("<H", n_params)
                   + b"\x00" + struct.pack("<H", 0))
        for _ in range(n_params):
            col = (_lenenc_str(b"def") + _lenenc_str(b"") * 3
                   + _lenenc_str(b"?") * 2 + bytes([0x0C])
                   + struct.pack("<H", 0x21) + struct.pack("<I", 1024)
                   + bytes([253]) + struct.pack("<H", 0) + b"\x00\x00\x00")
            self._send(col)
        if n_params:
            self.send_eof()

    def _handle_execute(self, body: bytes):
        stmt_id = int.from_bytes(body[0:4], "little")
        stmt = self._stmts.get(stmt_id)
        if stmt is None:
            raise ValueError(f"unknown statement id {stmt_id}")
        pos = 4 + 1 + 4                  # stmt_id, flags, iteration_count
        params = (_decode_binary_params(body, pos, stmt)
                  if stmt.n_params else [])
        r = self.session.execute(stmt.sql, params=params)
        self._result_to_packets(r, binary=True)

    # ---- command loop
    def run(self):
        try:
            nonce = self.send_handshake()
            if not self.authenticate(nonce):
                return
            self.send_ok()
            while True:
                pkt = self._recv()
                if pkt is None or pkt[0] == _COM_QUIT:
                    return
                cmd, body = pkt[0], pkt[1:]
                if cmd in (_COM_PING, _COM_INIT_DB):
                    self.seq = 1
                    self.send_ok()
                    continue
                if cmd == _COM_QUERY:
                    self.seq = 1
                    sql = body.decode("utf-8", "replace")
                    try:
                        r = self.session.execute(sql)
                    except Exception as e:  # noqa: BLE001 — wire ERR pkt
                        self.send_err(str(e))
                        continue
                    self._result_to_packets(r, binary=False)
                    continue
                if cmd == _COM_STMT_PREPARE:
                    self.seq = 1
                    try:
                        self._handle_prepare(body.decode("utf-8", "replace"))
                    except Exception as e:  # noqa: BLE001 — wire ERR pkt
                        self.send_err(str(e))
                    continue
                if cmd == _COM_STMT_EXECUTE:
                    self.seq = 1
                    try:
                        self._handle_execute(body)
                    except Exception as e:  # noqa: BLE001 — wire ERR pkt
                        self.send_err(str(e))
                    continue
                if cmd == _COM_STMT_CLOSE:
                    self._stmts.pop(int.from_bytes(body[0:4], "little"), None)
                    continue              # no response by protocol
                if cmd == _COM_STMT_RESET:
                    self.seq = 1
                    self.send_ok()
                    continue
                self.send_err(f"unsupported command 0x{cmd:02x}")
        except (OSError, ConnectionError):
            return   # client went away mid-exchange; nothing to clean up
        finally:
            if self.session is not None:
                self.session.close()   # release the processlist slot
            try:
                self.sock.close()
            except OSError:
                pass


def _decimal_text(scaled: int, scale: int) -> str:
    """A DECIMAL64 as the wire sends it: every digit of the scaled
    integer (a float loses them past 2^53, which an SF1 sum reaches),
    in the shape `str(float)` gave small values: trailing zeros dropped,
    one fractional digit kept."""
    digits = str(abs(scaled)).rjust(scale + 1, "0")
    whole, frac = digits[:len(digits) - scale], digits[len(digits) - scale:]
    return (("-" if scaled < 0 else "") + whole + "."
            + (frac.rstrip("0") or "0"))


def _wire_rows(result: Result):
    """`Result.rows()` with DECIMAL columns rendered exactly."""
    cols = []
    for name in result.column_names:
        vec = result.batch.columns[name]
        if vec.dtype.oid == TypeOid.DECIMAL64:
            cols.append([_decimal_text(int(v), vec.dtype.scale) if ok
                         else None
                         for v, ok in zip(vec.data, vec.valid_mask())])
        else:
            cols.append(vec.to_pylist())
    return list(zip(*cols))


class MOServer:
    """reference: frontend/server.go:611 NewMOServer / :99 Start.

    ``users`` maps username -> plaintext password (stored internally as
    SHA1(SHA1(pw)) stage2 hashes). Default: {"root": ""}. Pass
    ``insecure=True`` to skip credential verification entirely."""

    def __init__(self, engine=None, host: str = "127.0.0.1", port: int = 6001,
                 users: Optional[Dict[str, str]] = None,
                 insecure: bool = False):
        from matrixone_tpu.storage.engine import Engine
        self.engine = engine if engine is not None else Engine()
        self.host = host
        self.port = port
        if users is None:
            users = {"root": ""}
        # empty-password accounts are marked with b"" (expect an empty
        # scramble); others store the stage2 hash
        self.users = {u: (password_stage2(p) if p else b"")
                      for u, p in users.items()}
        self.insecure = insecure
        self.auth_mgr = None
        self._sock: Optional[socket.socket] = None
        self._thread: Optional[threading.Thread] = None
        self._stopping = threading.Event()

    def make_session(self, ctx) -> Session:
        return Session(catalog=self.engine, auth=ctx,
                       auth_manager=self.auth_mgr)

    def start(self):
        from matrixone_tpu.utils import enable_compilation_cache
        enable_compilation_cache()
        if not self.insecure:
            # accounts/users/roles live in engine tables and replicate
            # through the logtail; the seeded users land in the sys
            # account (frontend/auth.py)
            from matrixone_tpu.frontend.auth import AccountManager
            self.auth_mgr = AccountManager(self.engine,
                                           seed_users=dict(self.users))
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((self.host, self.port))
        self.port = self._sock.getsockname()[1]
        self._sock.listen(64)
        self._svc = ServiceThreads("mo-server")
        self._thread = self._svc.spawn_accept(self._accept_loop)
        return self

    def _accept_loop(self):
        while not self._stopping.is_set():
            try:
                sock, _ = self._sock.accept()
            except OSError:
                return
            conn = _Conn(sock, self)
            self._svc.spawn_handler(lambda s, c=conn: c.run(), sock)

    def stop(self):
        self._stopping.set()
        if self._sock is not None:
            # interrupt blocked accept + session recv()s and JOIN with a
            # deadline (mosan leak checker gates abandoned threads)
            self._svc.shutdown(self._sock)
