"""The one traffic generator of the serving cells.

A traffic file gives the multiset of (prompt length, new tokens) pairs, the
loop (``open`` with a rate and an initial burst, or ``closed`` with a number
of clients) and nothing else the generator needs; ``--seed`` permutes the
multiset (a fresh permutation each time it is exhausted), draws the token ids
and the arrival gaps.  Two seeds offer the same work in another order.

Open loop: a request is sent at its due time whatever the server does, and is
timed from its due time; how late the generator ran is reported.  Closed
loop: each client sends its next request when the previous one is answered.
"""
from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np


def plan(traffic, seed, vocab_size, horizon_s):
    """The requests of a run, in order: ``{"due", "prompt", "new"}``.  For an
    open loop enough of them to cover ``horizon_s`` seconds of arrivals
    (``burst`` at time 0, then Poisson at ``rate_rps``); for a closed loop
    ``due`` is None and there are ``max_requests`` of them."""
    rng = np.random.RandomState(seed % (2 ** 32))
    pairs = [tuple(p) for p in traffic["pairs"]]
    out = []

    def extend():
        for i in rng.permutation(len(pairs)):
            n_prompt, n_new = pairs[i]
            out.append({"prompt": rng.randint(0, vocab_size, n_prompt).tolist(),
                        "new": int(n_new), "due": None})

    if traffic["loop"] == "open":
        dues = [0.0] * int(traffic["burst"])
        t = 0.0
        while t < horizon_s:
            t += rng.exponential(1.0 / float(traffic["rate_rps"]))
            dues.append(t)
        while len(out) < len(dues):
            extend()
        del out[len(dues):]
        for r, due in zip(out, dues):
            r["due"] = due
    elif traffic["loop"] == "closed":
        while len(out) < int(traffic["max_requests"]):
            extend()
        del out[int(traffic["max_requests"]):]
    else:
        raise ValueError("loop must be open or closed, not %r"
                         % (traffic["loop"],))
    for r in out:
        r["body"] = json.dumps({"tokens": r["prompt"],
                                "max_new_tokens": r["new"],
                                "temperature": 0.0,
                                "timeout_ms": 3600000}).encode()
    return out


def post(port, path, body, timeout=3600.0):
    """(status, parsed reply or None)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, None
    except (OSError, http.client.HTTPException):
        return 0, None
    finally:
        conn.close()


class Load:
    """Drives ``requests`` at a server and keeps what came back."""

    def __init__(self, traffic, requests, port, path):
        self.traffic, self.requests = traffic, requests
        self.port, self.path = port, path
        self.records = []               # one per request sent
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self._next = 0
        self.t0 = None
        self.late_s = []

    def _send(self, i, t_from):
        r = self.requests[i]
        status, reply = post(self.port, self.path, r["body"])
        t_done = time.perf_counter()
        tokens = reply.get("tokens") if isinstance(reply, dict) else None
        rec = {"index": i, "t_from": t_from, "t_done": t_done,
               "status": status, "tokens": tokens}
        with self._lock:
            self.records.append(rec)

    def _dispatcher(self):
        for i, r in enumerate(self.requests):
            due = self.t0 + r["due"]
            delay = due - time.perf_counter()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            self.late_s.append(time.perf_counter() - due)
            t = threading.Thread(target=self._send, args=(i, due),
                                 name="chipbench-req", daemon=True)
            t.start()
            self._threads.append(t)

    def _client(self, k):
        # clients start evenly over ``ramp_s``: callers that all start in
        # the same instant stay in lockstep for ever (every one prefilled,
        # then every one decoded), which no population of callers does
        ramp = float(self.traffic.get("ramp_s", 0.0))
        if self._stop.wait(k * ramp / int(self.traffic["clients"])):
            return
        while not self._stop.is_set():
            with self._lock:
                i = self._next
                self._next += 1
            if i >= len(self.requests):
                return
            self._send(i, time.perf_counter())

    def start(self):
        self.t0 = time.perf_counter()
        if self.traffic["loop"] == "open":
            heads = [threading.Thread(target=self._dispatcher,
                                      name="chipbench-dispatch", daemon=True)]
        else:
            heads = [threading.Thread(target=self._client, args=(k,),
                                      name="chipbench-client-%d" % k,
                                      daemon=True)
                     for k in range(int(self.traffic["clients"]))]
        self._heads = heads
        for t in heads:
            t.start()

    def stop(self):
        """No new request is sent from here on."""
        self._stop.set()

    def join(self, timeout):
        deadline = time.perf_counter() + timeout
        for t in self._heads + self._threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self._heads + self._threads)
