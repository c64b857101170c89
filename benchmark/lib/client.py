"""Closed-loop HTTP clients for a served LM: each client posts, waits for the
reply, posts the next.  The loop and the failure classes are copied from
``tools/load_gen.py::run_load``; what differs is where the requests come
from: the sizes are a literal table in the traffic file, walked in a fixed
order, and only the token ids come from the seed."""

from __future__ import annotations

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy


def failure_class(code, exc):
    if code == 200:
        return "ok"
    if code == 429:
        return "http_429"
    if code == 503:
        return "http_503"
    if code:
        return "http_other"
    reason = getattr(exc, "reason", exc)
    if isinstance(reason, (socket.timeout, TimeoutError)):
        return "timeout"
    return "connection"


def request_sizes(traffic, client, index):
    """(table entry, prompt tokens, n_new) of a client's ``index``-th
    request: client c starts at entry c and takes the next entry after each
    reply, wrapping.  Nothing here depends on the seed."""
    table = traffic["table"]
    entry = (client + index) % len(table)
    return entry, int(table[entry][0]), int(table[entry][1])


def prompt_tokens(seed, client, index, length, vocab):
    """Token ids uniform over the vocabulary: all that the seed decides."""
    rng = numpy.random.default_rng([int(seed), client, index])
    return rng.integers(0, vocab, size=length, dtype=numpy.int64).tolist()


class ClosedLoop:
    """``traffic["clients"]`` threads against ``url``; ``log`` collects one
    record per request sent (times on ``time.monotonic``)."""

    def __init__(self, url, traffic, seed, vocab):
        self.url = url
        self.traffic = traffic
        self.seed = seed
        self.vocab = vocab
        self.log = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._client, args=(c,), daemon=True,
                             name="bench-client-%d" % c)
            for c in range(int(traffic["clients"]))]

    def start(self):
        for t in self._threads:
            t.start()
        return self

    def stop(self, wait_s):
        """No new requests; wait up to ``wait_s`` for the replies in flight.
        Returns the number of clients that did not come back."""
        self._stop.set()
        deadline = time.monotonic() + wait_s
        for t in self._threads:
            t.join(max(0.0, deadline - time.monotonic()))
        return sum(t.is_alive() for t in self._threads)

    def snapshot(self):
        with self._lock:
            return list(self.log)

    def _client(self, c):
        timeout = float(self.traffic["request_timeout_s"])
        index = 0
        while not self._stop.is_set():
            entry, length, n_new = request_sizes(self.traffic, c, index)
            prompt = prompt_tokens(self.seed, c, index, length, self.vocab)
            data = json.dumps({"input": [prompt], "n_new": n_new}).encode()
            req = urllib.request.Request(
                self.url, data=data,
                headers={"Content-Type": "application/json"})
            rec = {"client": c, "index": index, "entry": entry,
                   "prompt_len": length, "n_new": n_new,
                   "t_send": time.monotonic(), "t_done": None,
                   "code": None, "class": None, "tokens": None}
            with self._lock:
                self.log.append(rec)
            out, code, exc = None, 0, None
            try:
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    out = json.loads(resp.read())
                    code = resp.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception as e:  # noqa: BLE001 — connection-level
                exc = e
            done = time.monotonic()
            tokens = None
            if code == 200 and isinstance(out, dict):
                rows = out.get("tokens")
                if isinstance(rows, list) and len(rows) == 1:
                    tokens = rows[0]
            with self._lock:
                rec.update(t_done=done, code=code,
                           **{"class": failure_class(code, exc)},
                           tokens=tokens, prompt=prompt)
            index += 1
