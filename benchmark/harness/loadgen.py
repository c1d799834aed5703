"""One open-loop load generator process: sockets and the clock only.

Run as a script by the HTTP driver (never imported by it, and it never
imports jax: the parent holds the chip, and the server's threads must
not share an interpreter lock with the generator). One thread, one
``selectors`` loop: each request is sent at its due time on an idle
keep-alive connection. Latency counts from the DUE time, so a slow
server never hides behind the client; how late the send really was is
recorded beside it.

A client of a real deployment (an application server) keeps a bounded
pool of connections, so ``pool`` connections are opened at a gentle
pace before the schedule starts, and a request that finds them all
busy waits in the client for the next free one, its latency still
running from its due time. The pool is never outgrown: a generator
that opened a connection for every waiting request turned one stall of
the machine into a flood of connects, which the server's short accept
queue dropped for seconds (chip call F of PR 22). A connection the
server closed is replaced, without blocking, when it is next needed.

argv: host port schedule.npz out.npz epoch timeout_s pool
"""

from __future__ import annotations

import collections
import errno
import json
import selectors
import socket
import sys
import time

import numpy as np


def _well_formed(body: bytes, num: int) -> bool:
    try:
        scores = json.loads(body)["itemScores"]
    except (ValueError, KeyError, TypeError):
        return False
    return (isinstance(scores, list) and 0 < len(scores) <= num
            and all(isinstance(s, dict) and isinstance(s.get("item"), str)
                    and isinstance(s.get("score"), (int, float))
                    for s in scores))



class _Conn:
    __slots__ = ("sock", "buf", "req", "need", "pending")

    def __init__(self, addr, block: bool):
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        if block:
            self.sock.settimeout(5.0)
            self.sock.connect(addr)
            self.sock.setblocking(False)
        else:
            self.sock.setblocking(False)
            err = self.sock.connect_ex(addr)
            if err not in (0, errno.EINPROGRESS):
                self.sock.close()
                raise OSError(err, "connect")
        self.buf = bytearray()
        self.req = -1
        self.need = -1
        self.pending = b""      # the request to send once connected


def run(host: str, port: int, sched_path: str, out_path: str,
        epoch: float, timeout_s: float, pool: int) -> None:
    z = np.load(sched_path)
    due, num, ends = z["due"], z["num"], z["ends"]
    blob = z["blob"].tobytes()
    starts = np.concatenate([[0], ends[:-1]])
    n = len(due)
    head = (f"POST /queries.json HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\nContent-Length: ").encode()
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    status = np.zeros(n, dtype=np.int32)
    ok = np.zeros(n, dtype=bool)
    # select() takes a microsecond timeout; epoll rounds up to whole
    # milliseconds, which would make every send up to 1 ms late
    sel = selectors.SelectSelector()
    idle: list = []
    inflight: dict = {}
    waiting: collections.deque = collections.deque()
    pool = max(1, pool)
    addr = (host, port)
    i = 0
    clock = time.time

    def dispatch(r: int) -> bool:
        """Send request ``r`` on an idle connection, or on a new one
        while the pool is not full. False when all are busy."""
        if not idle and len(inflight) >= pool:
            return False
        body = blob[starts[r]:ends[r]]
        msg = head + str(len(body)).encode() + b"\r\n\r\n" + body
        try:
            if idle:
                conn = idle.pop()
                conn.req = r
                sent[r] = clock() - epoch
                if conn.sock.send(msg) != len(msg):
                    raise OSError("short send")
                sel.register(conn.sock, selectors.EVENT_READ, conn)
            else:
                conn = _Conn(addr, block=False)
                conn.req = r
                conn.pending = msg
                sel.register(conn.sock, selectors.EVENT_WRITE, conn)
            inflight[conn.sock.fileno()] = conn
        except OSError:
            done[r] = clock() - epoch
            status[r] = -1
        return True

    def finish(conn, code: int, good: bool, reuse: bool) -> None:
        r = conn.req
        done[r] = clock() - epoch
        status[r] = code
        ok[r] = good
        sel.unregister(conn.sock)
        del inflight[conn.sock.fileno()]
        conn.buf.clear()
        conn.req = conn.need = -1
        if reuse:
            idle.append(conn)
        else:
            conn.sock.close()

    first = float(due[0]) if n else 0.0
    for _ in range(pool):
        if clock() - epoch > first - 0.2:
            break
        idle.append(_Conn(addr, block=True))
        time.sleep(0.002)

    while i < n or inflight or waiting:
        now = clock() - epoch
        while i < n and due[i] <= now:
            waiting.append(i)
            i += 1
        while waiting:
            r = waiting[0]
            if now - due[r] > timeout_s:     # never got a connection
                done[r], status[r] = now, -2
            elif not dispatch(r):
                break
            waiting.popleft()
        now = clock() - epoch
        wait = 0.05 if i >= n else max(0.0, min(0.05, due[i] - now))
        for key, _ in sel.select(wait):
            conn = key.data
            if conn.pending:
                # the connect finished, one way or the other
                err = conn.sock.getsockopt(socket.SOL_SOCKET,
                                           socket.SO_ERROR)
                msg, conn.pending = conn.pending, b""
                try:
                    if err:
                        raise OSError(err, "connect")
                    sent[conn.req] = clock() - epoch
                    if conn.sock.send(msg) != len(msg):
                        raise OSError("short send")
                    sel.modify(conn.sock, selectors.EVENT_READ, conn)
                except OSError:
                    finish(conn, -1, False, reuse=False)
                continue
            try:
                chunk = conn.sock.recv(65536)
            except BlockingIOError:
                continue
            except OSError:
                chunk = b""
            if not chunk:
                finish(conn, -1, False, reuse=False)
                continue
            conn.buf += chunk
            if conn.need < 0:
                end = conn.buf.find(b"\r\n\r\n")
                if end < 0:
                    continue
                headers = bytes(conn.buf[:end]).lower()
                at = headers.find(b"content-length:")
                length = int(headers[at + 15:].split(b"\r\n", 1)[0]) \
                    if at >= 0 else 0
                conn.need = end + 4 + length
            if len(conn.buf) >= conn.need:
                code = int(bytes(conn.buf[9:12]) or 0)
                end = conn.buf.find(b"\r\n\r\n")
                good = code == 200 and _well_formed(
                    bytes(conn.buf[end + 4:conn.need]), int(num[conn.req]))
                keep = b"connection: close" not in bytes(
                    conn.buf[:end]).lower()
                finish(conn, code, good, reuse=keep)
        now = clock() - epoch
        for conn in [c for c in inflight.values()
                     if now - due[c.req] > timeout_s]:
            finish(conn, -2, False, reuse=False)
    for conn in idle:
        conn.sock.close()
    np.savez(out_path, index=z["index"], due=due, sent=sent, done=done,
             status=status, ok=ok)


if __name__ == "__main__":
    a = sys.argv[1:]
    run(a[0], int(a[1]), a[2], a[3], float(a[4]), float(a[5]), int(a[6]))
