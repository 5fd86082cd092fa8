"""The seeded length generator and the closed-loop pump."""

import numpy as np

from benchmarks.lib import loadgen

SPEC = {"pool": 512, "pool_seed": 23,
        "prompt": {"median": 192, "sigma": 0.6, "min": 32, "max": 640},
        "output": {"median": 96, "sigma": 0.6, "min": 16, "max": 320}}


def test_lengths_are_deterministic_and_clipped():
    a = loadgen.request_lengths(SPEC)
    b = loadgen.request_lengths(SPEC)
    assert (a == b).all() and a.shape == (512, 2)
    assert a[:, 0].min() >= 32 and a[:, 0].max() <= 640
    assert a[:, 1].min() >= 16 and a[:, 1].max() <= 320
    assert (a.sum(axis=1) <= 960).all()       # prompt + output < max_len
    # the medians the cell states, and both clips are really reached
    assert 170 <= np.median(a[:, 0]) <= 215
    assert 85 <= np.median(a[:, 1]) <= 108
    assert (a[:, 0] == 640).any() and (a[:, 1] == 320).any()


def test_the_pool_seed_and_nothing_else_changes_the_sizes():
    a = loadgen.request_lengths(SPEC)
    other = loadgen.request_lengths({**SPEC, "pool_seed": 24})
    assert not (a == other).all()
    d = loadgen.describe(a)
    assert d["prompt"]["min"] == a[:, 0].min()
    assert d["output"]["max"] == a[:, 1].max()


class FakeScheduler:
    """Every live request gains one token a tick and ends after three."""

    def __init__(self):
        self.tokens, self.t = {}, 0.0

    def clock(self):
        return self.t

    def submit(self, number):
        self.tokens[number] = 0
        return number

    def tick(self):
        self.t += 1.0
        for rid in self.tokens:
            if self.tokens[rid] < 3:
                self.tokens[rid] += 1

    def poll(self, rid):
        n = self.tokens[rid]
        return n, ("done" if n >= 3 else "running")


def test_closed_loop_keeps_every_client_busy_and_times_from_hand_over():
    s = FakeScheduler()
    loop = loadgen.ClosedLoop(clients=2, submit=s.submit, tick=s.tick,
                              poll=s.poll, clock=s.clock)
    while len(loop.ended) < 4:
        loop.step()
    # two clients, three ticks a request: requests 0,1 end at t=3 and their
    # successors 2,3 are handed over then and end at t=6
    assert [e["number"] for e in loop.ended] == [0, 1, 2, 3]
    assert [e["when"] for e in loop.ended] == [3.0, 3.0, 6.0, 6.0]
    assert len(loop.live) == 2 and loop.next_request == 6
    assert [v for _, v in loop.first_tokens] == [1.0] * 4
    assert [v for _, v in loop.gaps] == [1.0] * 8
    assert [d for _, d in loop.ticks] == [1.0] * 6
    assert loadgen.in_window(loop.first_tokens, 1.0, 4.0) == [1.0, 1.0]
