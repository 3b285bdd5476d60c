"""The open-loop load generator: a process of its own, off jax.

    python3 -m benchmark.loadgen <spec.json>

Reads a spec (port, mix, rate, lengths, seed), builds the schedule with
``benchmark.traffic``, opens its connections, prints ``READY`` and waits
on standard input for the epoch second at which the schedule starts (so
it can get ready while the server still warms up). Then it sends every
request at its due instant whether or not earlier answers came. One thread, one event loop, keep-alive connections opened ahead
and more on demand, so no request waits for another's connection. Each
request is timed from the instant it was DUE. When the last answer is
in (or its time limit has passed) it writes one ``.npz`` with the
per-request arrays and the bodies of a seeded sample, and exits.
"""

from __future__ import annotations

import asyncio
import json
import socket
import sys
import time

import numpy as np

from benchmark import traffic

#: request states in the ``status`` array beyond HTTP codes
PENDING, TIMED_OUT, CONN_LOST = 0, -1, -2


class Conn(asyncio.Protocol):
    """One keep-alive HTTP/1.1 connection, one request in flight."""

    __slots__ = ("gen", "transport", "buf", "need", "head_end", "status",
                 "idx")

    def __init__(self, gen: "Generator") -> None:
        self.gen = gen
        self.transport = None
        self.buf = bytearray()
        self.need = -1
        self.head_end = 0
        self.status = 0
        self.idx = -1

    def connection_made(self, transport) -> None:
        self.transport = transport
        sock = transport.get_extra_info("socket")
        if sock is not None:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def data_received(self, data: bytes) -> None:
        buf = self.buf
        buf += data
        while True:
            if self.need < 0:
                end = buf.find(b"\r\n\r\n")
                if end < 0:
                    return
                head = bytes(buf[:end]).lower()
                self.status = int(head[9:12])
                at = head.find(b"content-length:")
                eol = head.find(b"\r\n", at)
                clen = int(head[at + 15: eol if eol >= 0 else None])
                self.head_end = end + 4
                self.need = end + 4 + clen
            if len(buf) < self.need:
                return
            body = bytes(buf[self.head_end:self.need])
            del buf[:self.need]
            self.need = -1
            self.gen.answered(self, self.status, body)

    def connection_lost(self, exc) -> None:
        self.gen.lost(self)


class Generator:
    def __init__(self, spec: dict) -> None:
        self.spec = spec
        mix = traffic.load_mix(spec["traffic"])
        self.mix = mix
        self.warm_s = float(spec["warm_s"])
        self.seconds = float(spec["seconds"])
        total_s = self.warm_s + self.seconds
        due, users = traffic.schedule(mix, float(spec["rate"]), total_s,
                                      int(spec["n_users"]),
                                      int(spec["seed"]))
        self.due = due
        self.users = users
        n = len(due)
        num = int(mix["query"]["num"])
        self.num = num
        self.payloads = [self._request(u, num) for u in users.tolist()]
        self.sent = np.zeros(n)
        self.done = np.zeros(n)
        self.status = np.zeros(n, np.int32)
        self.bodies = [None] * n
        self.free: list = []
        self.opened = 0
        self.outstanding = 0
        self.finished = asyncio.Event()
        self.all_sent = False

    @staticmethod
    def _request(user_row: int, num: int) -> bytes:
        body = json.dumps({"user": f"u{user_row}", "num": num}).encode()
        return (b"POST /queries.json HTTP/1.1\r\nHost: bench\r\n"
                b"Content-Type: application/json\r\nContent-Length: "
                + str(len(body)).encode() + b"\r\n\r\n" + body)

    # -- connection pool ---------------------------------------------------
    async def open_conn(self) -> Conn:
        loop = asyncio.get_running_loop()
        _t, conn = await loop.create_connection(
            lambda: Conn(self), "127.0.0.1", int(self.spec["port"]))
        self.opened += 1
        return conn

    def lost(self, conn: Conn) -> None:
        if conn in self.free:
            self.free.remove(conn)
        if conn.idx >= 0:
            self._finish(conn.idx, CONN_LOST, b"")
            conn.idx = -1

    def answered(self, conn: Conn, status: int, body: bytes) -> None:
        idx = conn.idx
        conn.idx = -1
        self.free.append(conn)
        if idx >= 0 and self.status[idx] == PENDING:
            self._finish(idx, status, body)

    def _finish(self, idx: int, status: int, body: bytes) -> None:
        self.done[idx] = self.loop.time() - self.t0
        self.status[idx] = status
        self.bodies[idx] = body
        self.outstanding -= 1
        if self.all_sent and self.outstanding == 0:
            self.finished.set()

    # -- sending -----------------------------------------------------------
    def fire(self, idx: int) -> None:
        self.outstanding += 1
        if self.free:
            self._send(self.free.pop(), idx)
        else:
            # every connection is busy: open one more rather than wait
            # for another request's answer (the wait shows as lateness)
            asyncio.ensure_future(self._send_new(idx))

    async def _send_new(self, idx: int) -> None:
        try:
            conn = await self.open_conn()
        except OSError:
            self._finish(idx, CONN_LOST, b"")
            return
        self._send(conn, idx)

    def _send(self, conn: Conn, idx: int) -> None:
        conn.idx = idx
        self.sent[idx] = self.loop.time() - self.t0
        conn.transport.write(self.payloads[idx])

    async def run(self) -> None:
        self.loop = loop = asyncio.get_running_loop()
        for _ in range(int(self.mix.get("connections", 256))):
            self.free.append(await self.open_conn())
        print("READY", flush=True)
        # the parent fixes the epoch second at which the schedule starts
        line = await loop.run_in_executor(None, sys.stdin.readline)
        self.start_at = float(line)
        self.t0 = loop.time() + (self.start_at - time.time())
        if self.t0 < loop.time():
            raise SystemExit("loadgen: started after its own schedule")
        for idx, t in enumerate(self.due.tolist()):
            loop.call_at(self.t0 + t, self.fire, idx)
        end = self.t0 + self.warm_s + self.seconds
        await asyncio.sleep(max(end - loop.time(), 0) + 0.01)
        self.all_sent = True
        if self.outstanding:
            limit = float(self.mix["time_limit_s"])
            try:
                await asyncio.wait_for(self.finished.wait(), limit)
            except asyncio.TimeoutError:
                pass
        for idx in np.flatnonzero(self.status == PENDING).tolist():
            self.done[idx] = loop.time() - self.t0
            self.status[idx] = TIMED_OUT
        for conn in list(self.free):
            conn.transport.close()

    # -- result ------------------------------------------------------------
    def well_formed(self, body: bytes) -> bool:
        return (body.startswith(b'{"itemScores": [') and body.endswith(b"]}")
                and body.count(b'"item":') == self.num)

    def write(self, path: str) -> None:
        timed = self.due >= self.warm_s
        ok = self.status == 200
        formed = np.array([ok[i] and self.well_formed(self.bodies[i])
                           for i in range(len(ok))], bool)
        # an answer later than the client's limit is a failure, whenever
        # it came
        in_time = (self.done - self.due) <= float(self.mix["time_limit_s"])
        good = formed & in_time
        pool = np.flatnonzero(timed & good)
        rng = np.random.default_rng([int(self.spec["seed"]), 0x5a3])
        take = min(int(self.spec["sample"]), len(pool))
        pick = np.sort(rng.choice(pool, take, replace=False)) if take \
            else np.zeros(0, np.int64)
        picked = [self.bodies[i] for i in pick.tolist()]
        np.savez(
            path, due=self.due, sent=self.sent, done=self.done,
            status=self.status, good=good, timed=timed, users=self.users,
            sample_idx=pick,
            sample_bytes=np.frombuffer(b"".join(picked), np.uint8),
            sample_ends=np.cumsum([len(b) for b in picked], dtype=np.int64),
            opened=self.opened, t0_epoch=self.start_at)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        spec = json.load(f)
    gen = Generator(spec)
    asyncio.run(gen.run())
    gen.write(spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
