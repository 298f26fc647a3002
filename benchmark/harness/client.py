#!/usr/bin/env python3
"""The load generator: one process, one thread, no JAX.

Started by the serving phase with a plan file; talks to the server over
HTTP as a user would (``POST /v1/generate`` with ``stream: true``) and
stamps every streamed token with its arrival time. It runs apart from
the server's process so that building and parsing requests never takes
the engine thread's interpreter lock.

Phases: warm-up (one request per program shape, one at a time), lead-in,
the window, and a short tail that lets requests due in the window get
their first token. It announces ``WINDOW_OPEN`` / ``WINDOW_CLOSE`` with
the wall-clock instant on its standard output, which the serving phase
reads, and writes every record to the plan's ``records`` file.
"""

from __future__ import annotations

import json
import os
import selectors
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import traffic as traffic_lib  # noqa: E402


def say(tag: str, **fields) -> None:
    print(json.dumps({"event": tag, **fields}), flush=True)


class Conn:
    """One streamed request: a socket and what has arrived on it."""

    def __init__(self, request, due: float, phase: str):
        self.request = request
        self.due = due
        self.phase = phase
        self.sent = None
        self.sock = None
        self.buf = b""
        self.headers_done = False
        self.status = None
        self.token_times: list[float] = []
        self.tokens: list[int] = []
        self.request_id = None
        self.done = False
        self.error = None

    def open(self, host: str, port: int, plan: dict) -> None:
        body = json.dumps({
            "tokens": [self.request.tokens],
            "max_new_tokens": self.request.max_new,
            "temperature": plan["temperature"],
            "eos_tokens": plan["eos_tokens"],
            "stream": True}).encode()
        head = (f"POST /v1/generate HTTP/1.1\r\nHost: {host}:{port}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
        self.sock = socket.create_connection((host, port), timeout=30)
        self.sent = time.time()
        self.sock.sendall(head.encode() + body)
        self.sock.setblocking(False)

    def feed(self, now: float) -> None:
        """Read what is there; stamp each token event with `now`."""
        try:
            chunk = self.sock.recv(65536)
        except BlockingIOError:
            return
        except OSError as exc:
            self.fail(f"{type(exc).__name__}: {exc}")
            return
        if not chunk:
            if not self.done:
                self.fail("connection closed before `done`")
            return
        self.buf += chunk
        if not self.headers_done:
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            status_line = self.buf[:self.buf.find(b"\r\n")].decode()
            self.status = int(status_line.split()[1])
            self.buf = self.buf[end + 4:]
            self.headers_done = True
            if self.status != 200:
                self.fail(f"HTTP {self.status}: {self.buf[:200]!r}")
                return
        while True:
            end = self.buf.find(b"\n\n")
            if end < 0:
                return
            event, self.buf = self.buf[:end].decode(), self.buf[end + 2:]
            kind, data = "message", None
            for line in event.split("\n"):
                if line.startswith("event: "):
                    kind = line[7:]
                elif line.startswith("data: "):
                    data = json.loads(line[6:])
            if kind == "message" and data is not None and "token" in data:
                self.tokens.append(int(data["token"]))
                self.token_times.append(now)
            elif kind == "done":
                ids = data.get("request_ids") or [None]
                self.request_id = ids[0]
                if data["tokens"][0] != self.tokens:
                    self.fail("streamed tokens differ from the final list")
                self.finish()
                return
            elif kind == "error":
                self.fail(str(data))
                return

    def fail(self, why: str) -> None:
        self.error = why
        self.finish()

    def finish(self) -> None:
        self.done = True
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass

    def record(self, keep_tokens: bool) -> dict:
        r = self.request
        out = {"index": r.index, "phase": self.phase, "due": self.due,
               "sent": self.sent, "prompt_len": len(r.tokens),
               "max_new": r.max_new, "prefix": r.prefix,
               "token_times": self.token_times, "n_out": len(self.tokens),
               "finished": self.done and not self.error
               and len(self.tokens) == r.max_new,
               "error": self.error, "request_id": self.request_id}
        if keep_tokens:
            out["prompt"] = r.tokens
            out["tokens"] = self.tokens
        return out


class Driver:
    def __init__(self, plan: dict):
        self.plan = plan
        self.host, self.port = plan["host"], plan["port"]
        self.sel = selectors.DefaultSelector()
        self.active: list[Conn] = []
        self.finished: list[Conn] = []

    def start(self, request, due: float, phase: str) -> Conn:
        conn = Conn(request, due, phase)
        try:
            conn.open(self.host, self.port, self.plan)
            self.sel.register(conn.sock, selectors.EVENT_READ, conn)
            self.active.append(conn)
        except OSError as exc:
            conn.sent = time.time()
            conn.fail(f"{type(exc).__name__}: {exc}")
            self.finished.append(conn)
        return conn

    def pump(self, timeout: float) -> None:
        for key, _ in self.sel.select(max(timeout, 0.0)):
            conn = key.data
            conn.feed(time.time())
            if conn.done:
                self.sel.unregister(key.fileobj)
                self.active.remove(conn)
                self.finished.append(conn)

    def drain(self, conns: list[Conn], limit_s: float) -> None:
        deadline = time.time() + limit_s
        while any(not c.done for c in conns) and time.time() < deadline:
            self.pump(0.05)

    def abandon(self) -> None:
        """Close what is still streaming: the server cancels a request
        whose client went away."""
        for conn in list(self.active):
            self.sel.unregister(conn.sock)
            conn.error = "abandoned after the window"
            conn.finish()
            self.finished.append(conn)
        self.active.clear()


def run(plan: dict) -> None:
    tr = plan["traffic"]
    stream = traffic_lib.Stream(tr, plan["seed"], plan["slots"], plan["vocab"])
    driver = Driver(plan)
    seconds = float(plan["seconds"])

    # Warm-up: every program shape once, one request at a time.
    for request in stream.warmup():
        conn = driver.start(request, time.time(), "warmup")
        driver.drain([conn], 1100.0)
        if conn.error or not conn.done:
            say("FAILED", why=f"warm-up request failed: {conn.error}")
            return
    say("WARM", t=time.time(), requests=len(driver.finished))

    blocks = iter(range(10 ** 9))
    pending: list = []

    def next_request():
        if not pending:
            pending.extend(stream.block(next(blocks)))
        return pending.pop(0)

    if tr["kind"] == "closed":
        clients = int(tr["clients_per_slot"]) * plan["slots"]
        lead = [driver.start(r, time.time(), "lead_in")
                for r in stream.lead_in()]
        t_open = t_close = None
        issued = 0
        while True:
            now = time.time()
            if t_open is None and all(c.done for c in lead):
                t_open = now
                t_close = t_open + seconds
                say("WINDOW_OPEN", t=t_open)
            if t_close is not None and now >= t_close:
                break
            while len(driver.active) < clients:
                driver.start(next_request(), time.time(),
                             "window" if t_open else "lead_in")
                issued += 1
                if issued <= clients:
                    time.sleep(0.002)   # the listener's backlog is 5
            driver.pump(0.02)
        say("WINDOW_CLOSE", t=t_close)
        driver.pump(0.0)
        driver.abandon()
    else:
        lead_span = float(tr.get("lead_in_blocks", 1)) * stream.block_size \
            / float(tr["rate_per_s"])
        origin = time.time() + 0.05
        t_open, t_close = origin + lead_span, origin + lead_span + seconds
        tail_limit = t_close + float(tr.get("tail_s", 20.0))
        announced_open = announced_close = False
        upcoming = next_request()
        while True:
            now = time.time()
            if not announced_open and now >= t_open:
                say("WINDOW_OPEN", t=t_open)
                announced_open = True
            if not announced_close and now >= t_close:
                say("WINDOW_CLOSE", t=t_close)
                announced_close = True
            if now >= t_close:
                # The tail: keep the arrivals coming until every request
                # due in the window has finished; nothing is drained
                # inside the window.
                waiting = [c for c in driver.active
                           if t_open <= c.due < t_close]
                if not waiting or now >= tail_limit:
                    break
            while origin + upcoming.due <= now:
                due = origin + upcoming.due
                phase = ("window" if t_open <= due < t_close else
                         "lead_in" if due < t_open else "tail")
                driver.start(upcoming, due, phase)
                upcoming = next_request()
            driver.pump(min(max(origin + upcoming.due - time.time(), 0.0),
                            0.02))
        driver.abandon()

    records = [c.record(keep_tokens=c.phase != "warmup")
               for c in driver.finished]
    with open(plan["records"], "w") as fh:
        json.dump({"t_open": t_open, "t_close": t_close,
                   "records": records}, fh)
    say("DONE", t=time.time(), requests=len(records))


if __name__ == "__main__":
    with open(sys.argv[1]) as fh:
        run(json.load(fh))
