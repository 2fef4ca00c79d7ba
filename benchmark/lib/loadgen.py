#!/usr/bin/env python3
"""Load generator: a child process of sockets, threads and clocks.

It never imports jax, so it does not touch the chip and does not share
the serving process's interpreter lock. The parent writes one JSON spec
line to stdin; the child answers ``{"ready": ...}`` on stdout when its
lead-in is armed, waits for ``{"t0": <monotonic seconds>}``, drives the
mix against the HTTP front door, and writes every request's record to
``spec["out"]`` before printing ``{"done": ...}``.

Clock: ``time.monotonic()`` — CLOCK_MONOTONIC is system-wide on Linux,
so the parent's and the child's readings are comparable.
"""
from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
from urllib.parse import urlparse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import traffic as T  # noqa: E402


class Recorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.records = []
        self.live = set()          # open sockets, shut down at the stop

    def add(self, rec):
        with self.lock:
            self.records.append(rec)


def send(host, port, rec, prompt, max_tokens, book: Recorder, stop,
         on_first=None):
    """One streamed completion over a socket of our own (the stop can
    then shut it down under a blocked read). Fills ``rec`` in place:
    sent, status, token times and ids, finish reason."""
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": True}).encode()
    head = (f"POST /v1/completions HTTP/1.0\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    sock = None
    try:
        sock = socket.create_connection((host, port), timeout=600)
        with book.lock:
            book.live.add(sock)
        rec["sent"] = time.monotonic()
        sock.sendall(head + body)
        fp = sock.makefile("rb")
        status = fp.readline().split()
        rec["status"] = int(status[1]) if len(status) > 1 else None
        while fp.readline() not in (b"\r\n", b"\n", b""):
            pass                                   # response headers
        if rec["status"] != 200:
            return
        while True:
            raw = fp.readline()
            if not raw:
                if not stop.is_set():
                    rec["error"] = "stream ended without [DONE]"
                return
            if not raw.startswith(b"data: "):
                continue
            now = time.monotonic()
            payload = raw[6:].strip()
            if payload == b"[DONE]":
                rec["done"] = now
                return
            choice = json.loads(payload)["choices"][0]
            if choice["token_id"] is not None:
                rec["t"].append(now)
                rec["tokens"].append(int(choice["token_id"]))
                if on_first is not None and len(rec["t"]) == 1:
                    on_first()
            else:
                rec["finish"] = choice["finish_reason"]
    except (OSError, ValueError) as e:
        if not stop.is_set():
            rec["error"] = repr(e)
    finally:
        if sock is not None:
            with book.lock:
                book.live.discard(sock)
            sock.close()


def new_record(index, prompt_len, max_tokens, due=None, client=None):
    return {"index": index, "prompt_len": prompt_len,
            "max_tokens": max_tokens, "due": due, "client": client,
            "sent": None, "status": None, "t": [], "tokens": [],
            "finish": None, "done": None, "error": None}


def run_backlog(spec, host, port, book, stop, ready):
    """``clients`` closed-loop clients, each replacing its finished
    request at once. Ready when every client has its first token."""
    clients = int(spec["traffic"]["clients"])
    plan = T.backlog_plan(spec["traffic"])
    first = [threading.Event() for _ in range(clients)]

    def client(c):
        k = 0
        while not stop.is_set():
            index, p, o = T.backlog_request(plan, clients, c, k)
            rec = new_record(index, p, o, client=c)
            book.add(rec)
            prompt = T.prompt_tokens(spec["seed"], index, p, spec["vocab"])
            send(host, port, rec, prompt, o, book, stop,
                 on_first=first[c].set)
            first[c].set()          # a refusal must not hang the ramp
            if rec["done"] is None:
                # a refused or broken request: do not spin on the server
                stop.wait(0.05)
            k += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for e in first:
        e.wait()
    ready()
    return threads


def run_open(spec, host, port, book, stop, ready):
    """Open loop: every request is sent at its due time whether or not
    earlier ones have finished. The lead-in starts at ``t0 - lead_s``."""
    sched = T.open_loop_schedule(spec["traffic"], spec["seed"],
                                 spec["seconds"])
    t0 = ready()
    start = t0 - float(spec["traffic"]["lead_s"])
    threads = []

    def dispatch():
        for index, (off, p, o) in enumerate(sched):
            due = start + off
            prompt = T.prompt_tokens(spec["seed"], index, p, spec["vocab"])
            delay = due - time.monotonic()
            if delay > 0 and stop.wait(delay):
                return
            if stop.is_set():
                return
            rec = new_record(index, p, o, due=due)
            book.add(rec)
            t = threading.Thread(target=send, daemon=True,
                                 args=(host, port, rec, prompt, o, book, stop))
            t.start()
            threads.append(t)

    d = threading.Thread(target=dispatch, daemon=True)
    d.start()
    threads.append(d)
    return threads


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    url = urlparse(spec["url"])
    book, stop = Recorder(), threading.Event()
    t0_box = []

    def ready():
        print(json.dumps({"ready": time.monotonic()}), flush=True)
        t0_box.append(float(json.loads(sys.stdin.readline())["t0"]))
        return t0_box[0]

    mode = {"backlog": run_backlog, "open": run_open}[spec["mode"]]
    threads = mode(spec, url.hostname, url.port, book, stop, ready)
    t_end = t0_box[0] + float(spec["seconds"]) + float(spec["drain_s"])
    if spec["mode"] == "open":
        # leave as soon as everything due has answered, else at the cap
        while time.monotonic() < t_end:
            if time.monotonic() > t0_box[0] + float(spec["seconds"]) \
                    and not any(t.is_alive() for t in threads):
                break
            time.sleep(0.02)
    else:
        time.sleep(max(0.0, t_end - time.monotonic()))
    stop.set()
    with book.lock:
        for sock in list(book.live):
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
    for t in threads:
        t.join(timeout=10)
    with book.lock:
        records = list(book.records)
    with open(spec["out"], "w") as f:
        json.dump({"t0": t0_box[0], "records": records}, f)
    print(json.dumps({"done": time.monotonic(), "requests": len(records)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
