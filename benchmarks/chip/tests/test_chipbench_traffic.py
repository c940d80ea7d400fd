"""The traffic generator, the load loops (on a stand-in engine), and the
end-to-end metric arithmetic."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import session  # noqa: E402
from chipbench.cell import Ctx, reader  # noqa: E402
from chipbench.traffic import (Traffic, exp_gaps, percentile,  # noqa: E402
                               quantile_pool)

BATCH = {"loop": "closed", "clients": 6,
         "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                    "lo": 32, "hi": 512},
         "output": {"dist": "uniform", "lo": 2, "hi": 6}}
CHAT = {"loop": "open", "arrivals": {"process": "poisson",
                                     "rate_per_s": 50.0},
        "sessions": {"count": 3, "history": {"dist": "uniform", "lo": 64,
                                             "hi": 128}},
        "prompt": {"dist": "uniform", "lo": 4, "hi": 8},
        "output": {"dist": "uniform", "lo": 2, "hi": 4}}
BIG = 2 ** 33 + 12345          # a seed past 32 bits


def test_same_seed_same_requests():
    a, b = Traffic(CHAT, BIG, 1000), Traffic(CHAT, BIG, 1000)
    for k in range(20):
        ra, rb = a.request(k), b.request(k)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.max_new, ra.due, ra.session) == (rb.max_new, rb.due,
                                                    rb.session)
    c = Traffic(CHAT, BIG + 1, 1000)
    assert not np.array_equal(a.request(0).prompt, c.request(0).prompt)


def test_seeds_share_the_sizes_in_another_order():
    a, b = Traffic(BATCH, 1, 100), Traffic(BATCH, BIG, 100)
    la = [len(a.request(k).prompt) for k in range(4096)]
    lb = [len(b.request(k).prompt) for k in range(4096)]
    assert sorted(la) == sorted(lb) and la != lb
    assert min(la) >= 32 and max(la) <= 512
    assert np.median(la) == pytest.approx(128, abs=1)


def test_sweep_gives_each_rate_turns_of_its_own():
    """``sweep.py`` serves each rate fresh turns after the same histories,
    with the same sizes and arrivals, so no rate finds an earlier rate's
    turns in the prefix cache."""
    import sweep
    a, b = (sweep.fresh_traffic(CHAT, BIG, 1000, salt) for salt in (0, 1))
    plain = Traffic(CHAT, BIG, 1000)
    assert all(np.array_equal(x, y) for x, y in zip(a.histories,
                                                   plain.histories))
    for k in range(20):
        ra, rb, rp = a.request(k), b.request(k), plain.request(k)
        assert (ra.max_new, ra.due, ra.session) == (rb.max_new, rb.due,
                                                    rb.session)
        assert (ra.max_new, ra.due, ra.session) == (rp.max_new, rp.due,
                                                    rp.session)
        hist = len(plain.histories[rp.session])
        assert np.array_equal(ra.prompt[:hist], rp.prompt[:hist])
        assert len(ra.prompt) == len(rb.prompt) == len(rp.prompt)
        assert not np.array_equal(ra.prompt[hist:], rb.prompt[hist:])


def test_quantile_pools():
    u = quantile_pool({"dist": "uniform", "lo": 16, "hi": 96}, 810)
    assert u.min() == 16 and u.max() == 96
    assert np.bincount(u)[16:].min() == 10          # every length, evenly
    g = exp_gaps(2.0)
    assert g.mean() == pytest.approx(0.5, rel=2e-3) and (g > 0).all()


def test_quantile_pools_take_steps():
    s = quantile_pool({"dist": "uniform", "lo": 1024, "hi": 4096,
                       "step": 32, "offset": 8}, 12)
    assert set(s % 32) == {8} and s.min() >= 1024 and s.max() <= 4096


def test_chunk_lengths_of_a_mix():
    assert Traffic(BATCH, 1, 100).chunk_lengths(32, 16) == sorted(
        {p % 32 for p in range(32, 513)} - {0} | {32})
    chat = {**CHAT, "sessions": {"count": 3, "history": {
        "dist": "uniform", "lo": 64, "hi": 128, "step": 32, "offset": 8}},
        "prompt": {"dist": "uniform", "lo": 32, "hi": 64, "step": 32}}
    assert Traffic(chat, 1, 100).chunk_lengths(32, 16) == [8, 32]


def test_a_small_pool_gives_every_window_the_same_sizes():
    mix = {**CHAT, "pool": 16}
    for seed in (1, BIG):
        t = Traffic(mix, seed, 1000)
        for k0 in (0, 16, 32):
            reqs = [t.request(k) for k in range(k0, k0 + 16)]
            assert sorted(r.max_new for r in reqs) == sorted(
                quantile_pool(CHAT["output"], 16))
        gaps = np.diff([0.0] + [t.request(k).due for k in range(16)])
        assert sorted(gaps) == pytest.approx(sorted(exp_gaps(
            CHAT["arrivals"]["rate_per_s"], 16)))


def test_sessions_history_then_turn():
    t = Traffic(CHAT, 7, 1000)
    assert len(t.histories) == 3
    assert all(64 <= len(h) <= 128 for h in t.histories)
    counts = np.bincount([t.request(k).session for k in range(30)])
    assert list(counts) == [10, 10, 10]
    r = t.request(4)
    h = t.histories[r.session]
    assert np.array_equal(r.prompt[:len(h)], h)
    assert 4 <= len(r.prompt) - len(h) <= 8
    assert t.longest() == 128 + 8 + 4
    assert Traffic(BATCH, 7, 100).longest() == 512 + 6


def test_open_loop_due_times():
    t = Traffic(CHAT, 3, 1000)
    due = [t.request(k).due for k in range(200)]
    assert all(b > a for a, b in zip(due, due[1:]))
    assert t.due_before(due[10]) == 10
    assert t.due_before(due[10] + 1e-9) == 11


class FakeEngine:
    """Stands in for ``PagedServer``: ``slots`` slots, one token per active
    request per step, a step of ``dt`` seconds."""

    def __init__(self, slots=4, dt=0.002):
        self.slots, self.dt = slots, dt
        self.pending, self.active, self.stats = [], {}, {}
        self.inflight_seen = []
        self.t0 = time.monotonic()

    def start_clock(self, reset=False):
        self.t0 = time.monotonic()

    def now(self):
        return time.monotonic() - self.t0

    def submit(self, req):
        self.pending.append(req)

    def poll(self):
        return bool(self.pending or self.active)

    def step(self):
        now = self.now()
        while (self.pending and self.pending[0].arrival <= now
               and len(self.active) < self.slots):
            r = self.pending.pop(0)
            self.active[r.rid] = [r, 0]
        self.inflight_seen.append(len(self.active) + len(self.pending))
        time.sleep(self.dt)
        done = {}
        for rid, st in list(self.active.items()):
            st[1] += 1
            st[0].on_token(rid, 7, self.now())
            if st[1] >= st[0].max_new:
                done[rid] = None
                del self.active[rid]
        self.stats["prefill_tokens"] = self.stats.get("prefill_tokens", 0)
        return done


def test_closed_loop_keeps_its_concurrency():
    eng = FakeEngine(slots=6)
    win = session.Window(eng, Traffic(BATCH, 5, 100), session.Spans(False))
    win.run(0.2)
    assert win.start is not None and win.end >= win.start + 0.2
    # every step saw exactly the clients' requests in flight
    assert set(eng.inflight_seen) == {6}
    assert len(win.recs) > 6
    finished = [r for r in win.recs.values() if r.done]
    assert all(len(r.times) == r.max_new for r in finished)
    # a client's next request is due when its last one finished
    assert all(r.due == r.submitted for r in win.recs.values())


def test_open_loop_submits_when_due():
    eng = FakeEngine(slots=8)
    tr = Traffic(CHAT, 5, 1000)
    win = session.Window(eng, tr, session.Spans(False))
    win.run(0.3)
    n = len(win.recs)
    assert n == tr.due_before(win.end - win.start) or n == tr.due_before(
        win.end - win.start) + 1
    for r in win.recs.values():
        assert r.due == pytest.approx(win.start + tr.request(r.k).due)
        assert 0 <= r.submitted - r.due < 0.05     # lateness, measured


def window_ctx(recs, start=10.0, end=20.0):
    win = session.Window(None, None, session.Spans(False))
    win.recs = {r.k: r for r in recs}
    win.start, win.end = start, end
    win.stats0, win.stats1 = {"occupancy": 1}, {"occupancy": 3}
    return Ctx(win=win, steps=[], stats={"occupancy": [0.1, 0.5, 1.0, 0.2]},
               times={"quantize_s": 5.0, "setup_s": 9.0,
                      "calibrate_s": 1.0})


def rec(k, due, times):
    return session.Rec(k=k, plen=10, max_new=10, due=due, times=times)


def test_end_to_end_arithmetic():
    ctx = window_ctx([
        rec(0, 5.0, [8.0, 11.0, 12.0]),    # due before the window
        rec(1, 11.0, [11.5, 12.5, 14.5]),
        rec(2, 12.0, [13.0, 21.0]),        # its last token after the close
        rec(3, 19.0, []),                  # no first token in the window
        rec(4, 15.0, [16.0]),
    ])
    # tokens inside (10, 20]: 2 + 3 + 1 + 1
    assert reader("output_tok_s")(ctx) == pytest.approx(7 / 10)
    # gaps ending inside: 3, 1 | 1, 2 | (21 is out)
    assert sorted(ctx.token_gaps()) == [1.0, 1.0, 2.0, 3.0]
    assert reader("itl_p95_ms")(ctx) == pytest.approx(
        1e3 * percentile([1, 1, 2, 3], 95))
    seen, missing = ctx.ttfts()
    assert sorted(seen) == [0.5, 1.0, 1.0] and missing == [1.0]
    # the missing request sorts after every seen one: [0.5, 1, 1, 1]
    assert reader("ttft_p50_ms")(ctx) == pytest.approx(1000.0)
    assert reader("quantize_s")(ctx) == 5.0
    assert reader("setup_s")(ctx) == 9.0
    assert reader("occupancy.batch")(ctx) == pytest.approx(75.0)


def test_percentile_counts_missing_as_latest():
    assert percentile([1, 2, 3], 50) == 2
    assert percentile([1, 2, 3], 50, missing=[0.5]) == 2.5
    assert percentile([1, 2, 3], 100, missing=[0.5]) == 3
    assert percentile([1, 2], 50, missing=[5, 6]) == 3.5
    assert percentile([10.0] * 19 + [20.0], 95) == pytest.approx(10.5)
