"""Front-door correctness (DESIGN.md §12): drop-and-replay preemption must
not change what a request generates, the scheduler's admission policies
(priority, weighted fair share, share cap, SLO hysteresis) must hold on a
deterministic fake engine, the SSE codec must round-trip, and the HTTP
server must boot, stream, and shut down cleanly as a subprocess."""
import collections
import dataclasses
import os
import signal
import subprocess
import sys
import threading
import time
import types
from pathlib import Path

import numpy as np
import pytest
from helpers import mixed_requests, small_pool, tiny

from repro.serve import Request
from repro.serve.frontdoor import SchedConfig, Scheduler
from repro.serve.frontdoor.sse import encode_event, iter_events

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------- SSE codec


def test_sse_round_trip():
    frames = [("token", {"rid": 0, "token": 7, "text": "a"}),
              ("token", {"rid": 0, "token": 9, "text": "\n"}),
              ("done", {"rid": 0, "tokens": [7, 9], "n_tokens": 2})]
    wire = b"".join(encode_event(e, d) for e, d in frames).decode()
    parsed = list(iter_events(wire.splitlines(keepends=True)))
    assert parsed == frames


def test_sse_parser_skips_comments_and_unterminated_tail():
    lines = [": keep-alive\n", "event: token\n", 'data: {"x": 1}\n', "\n",
             "event: token\n", 'data: {"never": "terminated"}\n']
    assert list(iter_events(lines)) == [("token", {"x": 1})]


def test_sse_multi_data_lines_join():
    lines = ["event: blob\n", "data: [1,\n", "data: 2]\n", "\n"]
    assert list(iter_events(lines)) == [("blob", [1, 2])]


# ------------------------------------------------------- scheduler policies


class FakePool:
    def __init__(self, max_slots):
        self.max_slots = max_slots


class FakeEngine:
    """Deterministic engine stub exposing exactly the surface Scheduler
    consumes: every admitted request 'decodes' one token per step and
    finishes after ``gen`` steps."""

    def __init__(self, max_slots=4, gen=100):
        self.pool = FakePool(max_slots)
        self.decode_gaps = collections.deque(maxlen=2048)
        self.gen = gen
        self.running = {}           # rid -> [req, done, t_admit]
        self.order = []             # admission order
        self.preempted = []
        self._t = 0.0

    def now(self):
        return self._t

    def validate(self, req):
        pass

    def can_admit(self, req):
        return len(self.running) < self.pool.max_slots

    def submit(self, req):
        self.running[req.rid] = [req, 0, self._t]
        self.order.append(req.rid)

    def poll(self):
        return bool(self.running)

    @property
    def active_count(self):
        return len(self.running)

    def inflight(self):
        return [(v[0], "decode", v[1], v[2]) for v in self.running.values()]

    def preempt(self, rid):
        if rid not in self.running:
            return None
        self.preempted.append(rid)
        return self.running.pop(rid)[0]

    def cancel(self, rid):
        return self.running.pop(rid, None) is not None

    def step(self, prefill=True):
        self._t += 1.0
        finished = {}
        for rid in list(self.running):
            self.running[rid][1] += 1
            if self.running[rid][1] >= self.gen:
                req = self.running.pop(rid)[0]
                finished[rid] = types.SimpleNamespace(
                    rid=rid, tenant=req.tenant)
        return finished


def _req(rid, tenant="default", priority=0):
    return Request(rid=rid, prompt=np.zeros(4, np.int32), max_new=4,
                   tenant=tenant, priority=priority)


def test_priority_admitted_before_fifo():
    eng = FakeEngine(max_slots=1)
    sched = Scheduler(eng)
    sched.submit(_req(0, priority=0))
    sched.submit(_req(1, priority=5))      # later submit, higher priority
    sched.tick()
    assert eng.order[0] == 1


def test_weighted_fair_share_split():
    eng = FakeEngine(max_slots=3)
    sched = Scheduler(eng)
    for i in range(4):
        sched.submit(_req(i, tenant="heavy"), weight=2.0)
    for i in range(4, 8):
        sched.submit(_req(i, tenant="light"), weight=1.0)
    sched.tick()
    held = collections.Counter(r.tenant for r, *_ in eng.inflight())
    assert held == {"heavy": 2, "light": 1}


def test_share_cap_binds_only_while_others_wait():
    # alone, a tenant may take every slot despite the cap...
    eng = FakeEngine(max_slots=4)
    sched = Scheduler(eng, SchedConfig(max_tenant_share=0.5))
    for i in range(4):
        sched.submit(_req(i, tenant="solo"))
    sched.tick()
    assert eng.active_count == 4
    # ...but with another tenant waiting, it is capped at ceil(0.5*4)=2
    eng = FakeEngine(max_slots=4)
    sched = Scheduler(eng, SchedConfig(max_tenant_share=0.5))
    for i in range(4):
        sched.submit(_req(i, tenant="greedy"))
    for i in range(4, 6):
        sched.submit(_req(i, tenant="other"))
    sched.tick()
    held = collections.Counter(r.tenant for r, *_ in eng.inflight())
    assert held["greedy"] == 2 and held["other"] == 2


def test_preempts_lower_priority_victim_and_requeues():
    eng = FakeEngine(max_slots=2)
    sched = Scheduler(eng)
    sched.submit(_req(0, priority=0))
    sched.submit(_req(1, priority=0))
    sched.tick()                           # pool full of priority-0 work
    sched.submit(_req(2, priority=5))
    sched.tick()                           # evicts one victim, requeues it
    assert sched.stats["preempted"] == 1
    assert len(eng.preempted) == 1
    sched.tick()                           # freed slot goes to the waiter
    assert 2 in eng.running
    # the victim is queued again, not lost
    assert sched.queued() + eng.active_count == 3


def test_no_preemption_when_disabled():
    eng = FakeEngine(max_slots=1)
    sched = Scheduler(eng, SchedConfig(preemption=False))
    sched.submit(_req(0, priority=0))
    sched.tick()
    sched.submit(_req(1, priority=5))
    sched.tick()
    assert eng.preempted == [] and sched.stats["preempted"] == 0


def test_slo_throttle_hysteresis():
    eng = FakeEngine(max_slots=2)
    sched = Scheduler(eng, SchedConfig(slo_p95_ms=10.0, slo_min_samples=4,
                                       slo_window=8, slo_resume_frac=0.5))
    sched.submit(_req(0))
    sched.tick()                                   # one active decoder
    assert sched.allow_prefill()                   # below min samples
    eng.decode_gaps.extend([0.020] * 8)            # p95 = 20ms > 10ms
    sched._update_slo()
    assert sched.throttled and not sched.allow_prefill()
    assert sched.stats["slo_throttle_on"] == 1
    eng.decode_gaps.extend([0.008] * 8)            # 8ms: below target but
    sched._update_slo()                            # above 0.5*10 = 5ms
    assert sched.throttled                         # hysteresis holds
    eng.decode_gaps.extend([0.004] * 8)            # 4ms < 5ms: resume
    sched._update_slo()
    assert not sched.throttled and sched.allow_prefill()
    assert sched.stats["slo_throttle_off"] == 1


def test_throttled_prefill_still_runs_when_pool_idle():
    eng = FakeEngine(max_slots=2)
    sched = Scheduler(eng, SchedConfig(slo_p95_ms=10.0, slo_min_samples=4))
    eng.decode_gaps.extend([0.020] * 8)
    sched._update_slo()
    assert sched.throttled
    assert eng.active_count == 0 and sched.allow_prefill()


def test_cancel_queued_and_running():
    eng = FakeEngine(max_slots=1)
    sched = Scheduler(eng)
    sched.submit(_req(0))
    sched.submit(_req(1))
    sched.tick()                           # 0 running, 1 queued
    assert sched.cancel(1) and sched.queued() == 0
    assert sched.cancel(0) and eng.active_count == 0
    assert not sched.cancel(99)


# --------------------------------------------- preemption parity (tier 2)


@pytest.mark.tier2
@pytest.mark.parametrize("arch", ["llama2-7b", "mixtral-8x7b"])
def test_preempt_replay_greedy_parity(arch):
    """A request preempted mid-decode and replayed must emit exactly the
    tokens of an uninterrupted run — on the cacheable dense arch (warm
    replay through the prefix cache) and the windowed MoE arch (cache
    bypassed, cold re-prefill of the served sequence)."""
    import jax
    from repro.models import transformer as tf
    from repro.serve import PagedServer

    cfg = tiny(arch)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    ref = PagedServer(cfg, params, small_pool()).run(mixed_requests(cfg))

    engine = PagedServer(cfg, params, small_pool())
    engine.start_clock()
    for r in mixed_requests(cfg):
        engine.submit(r)
    results, preempted = {}, False
    for _ in range(10_000):
        if not engine.poll():
            break
        results.update(engine.step())
        if not preempted:
            for req, phase, done, _t in engine.inflight():
                if req.rid == 0 and phase == "decode" and done >= 3:
                    victim = engine.preempt(0)
                    assert victim is not None
                    engine.submit(victim)
                    preempted = True
                    break
    assert preempted, "request 0 finished before it could be preempted"
    assert engine.stats["preemptions"] == 1
    assert results[0].preemptions == 1
    for rid, res in ref.items():
        np.testing.assert_array_equal(
            results[rid].tokens, res.tokens,
            err_msg=f"{arch}: rid={rid} diverged after preempt+replay")
    for res in results.values():
        assert res.ttft_s > 0.0
        assert len(res.token_times) == len(res.tokens)
        assert np.all(np.diff(res.token_times) >= 0)


@pytest.mark.tier2
def test_scheduler_end_to_end_on_real_engine():
    """Scheduler.tick over a real tiny engine: everything completes, and
    outputs match plain engine.run (admission order cannot change greedy
    tokens)."""
    import jax
    from repro.models import transformer as tf
    from repro.serve import PagedServer

    cfg = tiny("llama2-7b")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    ref = PagedServer(cfg, params, small_pool()).run(mixed_requests(cfg))

    engine = PagedServer(cfg, params, small_pool())
    engine.start_clock()
    sched = Scheduler(engine, SchedConfig(slo_p95_ms=1e6))
    for i, r in enumerate(mixed_requests(cfg)):
        sched.submit(dataclasses.replace(r, tenant=f"t{i % 2}",
                                         priority=i % 3))
    results = {}
    for _ in range(10_000):
        if not sched.has_work():
            break
        results.update(sched.tick())
    assert set(results) == set(ref)
    for rid, res in ref.items():
        np.testing.assert_array_equal(results[rid].tokens, res.tokens)


# ------------------------------------------------------ HTTP smoke (tier 2)


@pytest.mark.tier2
def test_http_smoke_stream_and_clean_shutdown():
    """Boot the front door as a subprocess, stream one generation over SSE
    via the bundled client, hit /healthz, then SIGTERM and require a clean
    exit — the same probe CI's serve-smoke leg runs."""
    # the CLI turns the persistent compile cache on; a test run keeps it off
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "llama2-7b",
         "--tiny", "--serve", "--port", "0", "--slots", "2",
         "--prompt-len", "32", "--gen", "16"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    lines = []
    reader = threading.Thread(
        target=lambda: lines.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    reader.start()
    try:
        port = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and port is None:
            for line in list(lines):
                if "frontdoor listening on" in line:
                    port = int(line.rsplit(":", 1)[1])
                    break
            assert proc.poll() is None, "server died:\n" + "".join(lines)
            time.sleep(0.5)
        assert port is not None, "no listening line:\n" + "".join(lines)

        from repro.serve.frontdoor.client import stream_generate
        events = list(stream_generate("127.0.0.1", port,
                                      prompt="the quick brown fox",
                                      max_new=8, timeout=120.0))
        tokens = [d for e, d in events if e == "token"]
        dones = [d for e, d in events if e == "done"]
        assert len(tokens) >= 1, events
        assert len(dones) == 1 and dones[0]["n_tokens"] == len(tokens)
        assert dones[0]["tokens"] == [t["token"] for t in tokens]

        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/healthz")
        assert conn.getresponse().status == 200
        conn.close()
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
        reader.join(timeout=5)
    assert rc == 0, f"unclean exit {rc}:\n" + "".join(lines)
    assert any("shut down cleanly" in line for line in lines)
