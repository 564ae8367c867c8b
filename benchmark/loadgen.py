"""The load generator: a child process that never imports jax.

It speaks the MySQL wire protocol with the standard library alone (the
query path of `matrixone_tpu/client.py`, copied so that the clock that
times a statement is the benchmark's own) and drives a closed loop:
`clients` connections, each sending its next statement when the previous
answer is complete.

Protocol with the parent (`run.py`), over the child's stdin / stdout, one
JSON object per line:

  parent -> child   {"port", "clients", "session", "statements", "starts"}
  child  -> parent  {"ready": true}             every client connected
  parent -> child   {"go": seconds}
  child  -> parent  {"t_start_ns", "t_close_ns", "t_last_done_ns",
                     "cpu_s", "results": [[client, statement, t_send_ns,
                     t_done_ns, rows | null, error | null], ...]}

Client i sends statements[(starts[i] + j) % len(statements)] for j = 0, 1,
...  At `seconds` the window closes to new statements; statements in
flight run to completion and count.  Times are `time.perf_counter_ns()`
(CLOCK_MONOTONIC, one clock for every process of the machine).
"""

import json
import os
import socket
import struct
import sys
import threading
import time


class WireError(RuntimeError):
    pass


class Connection:
    """COM_QUERY over the MySQL text protocol, no password."""

    def __init__(self, port, host="127.0.0.1", timeout=120.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.seq = 0
        greeting = self._recv()
        if greeting[0] != 10:
            raise WireError("unsupported protocol")
        caps = 0x0200 | 0x8000 | 0x00200000
        self._send(struct.pack("<I", caps) + struct.pack("<I", 1 << 24)
                   + bytes([0x21]) + b"\x00" * 23 + b"root\x00" + b"\x00"
                   + b"mysql_native_password\x00")
        resp = self._recv()
        if resp[0] == 0xFF:
            raise self._err(resp)

    def _send(self, payload):
        while True:
            chunk, payload = payload[:0xFFFFFF], payload[0xFFFFFF:]
            self.sock.sendall(struct.pack("<I", len(chunk))[:3]
                              + bytes([self.seq & 0xFF]) + chunk)
            self.seq += 1
            if len(chunk) < 0xFFFFFF:
                return

    def _recv_n(self, n):
        buf = bytearray()
        while len(buf) < n:
            part = self.sock.recv(n - len(buf))
            if not part:
                raise ConnectionError("server closed connection")
            buf += part
        return bytes(buf)

    def _recv(self):
        payload = b""
        while True:
            header = self._recv_n(4)
            length = int.from_bytes(header[:3], "little")
            self.seq = header[3] + 1
            payload += self._recv_n(length)
            if length < 0xFFFFFF:
                return payload

    @staticmethod
    def _lenenc(data, pos):
        b0 = data[pos]
        if b0 < 0xFB:
            return b0, pos + 1
        if b0 == 0xFB:
            return None, pos + 1
        if b0 == 0xFC:
            return int.from_bytes(data[pos + 1:pos + 3], "little"), pos + 3
        if b0 == 0xFD:
            return int.from_bytes(data[pos + 1:pos + 4], "little"), pos + 4
        return int.from_bytes(data[pos + 1:pos + 9], "little"), pos + 9

    @staticmethod
    def _err(payload):
        code = int.from_bytes(payload[1:3], "little")
        msg = payload[3:].decode("utf-8", "replace")
        return WireError(f"({code}) {msg[6:] if msg.startswith('#') else msg}")

    def query(self, sql):
        """-> rows as lists of text (None for NULL); [] for an OK packet.
        Returns when the last row has been received."""
        self.seq = 0
        self._send(b"\x03" + (sql if isinstance(sql, bytes)
                              else sql.encode()))
        first = self._recv()
        if first[0] == 0xFF:
            raise self._err(first)
        if first[0] == 0x00:
            return []
        ncols, _ = self._lenenc(first, 0)
        for _ in range(ncols):
            self._recv()
        self._recv()                      # EOF after the columns
        rows = []
        while True:
            pkt = self._recv()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return rows
            if pkt[0] == 0xFF:
                raise self._err(pkt)
            pos, row = 0, []
            for _ in range(ncols):
                ln, pos = self._lenenc(pkt, pos)
                if ln is None:
                    row.append(None)
                else:
                    row.append(pkt[pos:pos + ln].decode())
                    pos += ln
            rows.append(row)

    def close(self):
        try:
            self.seq = 0
            self._send(b"\x01")
        except OSError:
            pass
        self.sock.close()


def _client(conn, client, statements, start, go, close_at, out):
    """One closed loop.  `close_at` is a one-element list the main thread
    fills in before it sets `go`."""
    go.wait()
    j, n = start, len(statements)
    while True:
        t_send = time.perf_counter_ns()
        if t_send >= close_at[0]:
            return
        idx = j % n
        try:
            rows, err = conn.query(statements[idx]), None
        except (WireError, OSError) as e:
            rows, err = None, f"{type(e).__name__}: {e}"
        out.append([client, idx, t_send, time.perf_counter_ns(), rows, err])
        if err is not None:
            return                        # a broken connection stays broken
        j += 1


def main():
    spec = json.loads(sys.stdin.readline())
    statements = [s.encode() for s in spec["statements"]]
    conns = []
    for _ in range(spec["clients"]):
        c = Connection(spec["port"])
        for sql in spec["session"]:
            c.query(sql)
        conns.append(c)
    go, close_at, out = threading.Event(), [0], []
    threads = [threading.Thread(
        target=_client, daemon=True,
        args=(c, i, statements, spec["starts"][i], go, close_at, out))
        for i, c in enumerate(conns)]
    for t in threads:
        t.start()
    print(json.dumps({"ready": True}), flush=True)
    seconds = json.loads(sys.stdin.readline())["go"]
    cpu0 = sum(os.times()[:2])
    t_start = time.perf_counter_ns()
    close_at[0] = t_start + int(seconds * 1e9)
    go.set()
    for t in threads:
        t.join()
    for c in conns:
        c.close()
    out.sort(key=lambda r: r[2])
    print(json.dumps({
        "t_start_ns": t_start, "t_close_ns": close_at[0],
        "t_last_done_ns": max((r[3] for r in out), default=t_start),
        "cpu_s": sum(os.times()[:2]) - cpu0, "results": out}), flush=True)


if __name__ == "__main__":
    main()
