"""Seeded request lengths and the closed-loop pump.

The pump follows ``dtf_tpu.serve.client.replay`` (submit, tick while work is
pending) with two changes the verdict on that piece asked for: lengths come
from the cell's distribution, and every time is read on the benchmark's own
clock, from the request's hand-over.
"""

from __future__ import annotations

import math
import time
from typing import Callable

import numpy as np


def request_lengths(spec: dict) -> np.ndarray:
    """``spec["pool"]`` (prompt length, output length) pairs, log-normal
    round their medians and clipped, drawn from ``spec["pool_seed"]`` and
    served in that order, round and round: the same sizes in the same order
    for every ``--seed``, which changes the weights and the prompts' tokens
    and nothing else. (PR 23 first let the seed shuffle the order: a 30 s
    window then saw another hundred of the pool's requests with each seed,
    and tokens/s moved by 3% with the seed while two runs of one seed agreed
    to 0.5%. The seed was changing the work.)"""
    rng = np.random.default_rng(spec["pool_seed"])

    def draw(d):
        x = rng.lognormal(math.log(d["median"]), d["sigma"], spec["pool"])
        return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)

    return np.stack([draw(spec["prompt"]), draw(spec["output"])], axis=1)


def describe(lengths: np.ndarray) -> dict:
    """The drawn distribution, for the line the run prints."""
    out = {}
    for name, col in (("prompt", lengths[:, 0]), ("output", lengths[:, 1])):
        out[name] = {"min": int(col.min()),
                     "p50": float(np.percentile(col, 50)),
                     "p95": float(np.percentile(col, 95)),
                     "max": int(col.max()), "mean": float(col.mean())}
    return out


class ClosedLoop:
    """``clients`` callers, each handing over its next request the moment
    its last one ends. One thread: hand over, tick, look at what came back.

    ``submit(i) -> rid`` hands over request number ``i``; ``tick()`` runs one
    scheduler round; ``poll(rid) -> (n_tokens, status)``. After every tick
    the pump records, on ``clock``: a request's first token (time since its
    hand-over), every later token (gap since that request's previous
    token; several tokens landing in one tick share its end, so the later
    ones have gap 0), and a request's end.
    """

    def __init__(self, *, clients: int, submit: Callable[[int], int],
                 tick: Callable[[], None], poll: Callable,
                 clock=time.perf_counter):
        self.submit, self.tick, self.poll = submit, tick, poll
        self.clock = clock
        self.next_request = 0
        self.live: dict[int, dict] = {}   # rid -> bookkeeping
        self.ticks: list[tuple[float, float]] = []       # (end, duration)
        self.first_tokens: list[tuple[float, float]] = []  # (when, ttft_s)
        self.gaps: list[tuple[float, float]] = []          # (when, gap_s)
        self.ended: list[dict] = []       # when, rid, number, tokens, status
        for _ in range(clients):
            self._hand_over()

    def _hand_over(self):
        number = self.next_request
        self.next_request += 1
        rid = self.submit(number)
        self.live[rid] = {"number": number, "t_submit": self.clock(),
                          "seen": 0, "t_last": None}

    def step(self):
        t0 = self.clock()
        self.tick()
        now = self.clock()
        self.ticks.append((now, now - t0))
        for rid in list(self.live):
            book = self.live[rid]
            n, status = self.poll(rid)
            for _ in range(n - book["seen"]):
                if book["t_last"] is None:
                    self.first_tokens.append((now, now - book["t_submit"]))
                else:
                    self.gaps.append((now, now - book["t_last"]))
                book["t_last"] = now
            book["seen"] = n
            if status not in ("queued", "prefill", "running"):
                self.ended.append({"when": now, "rid": rid,
                                   "number": book["number"], "tokens": n,
                                   "status": status})
                del self.live[rid]
                self._hand_over()


def in_window(samples, t0: float, t1: float) -> list:
    """The values of ``(when, value)`` samples with ``t0 < when <= t1``."""
    return [v for when, v in samples if t0 < when <= t1]
