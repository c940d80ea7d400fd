"""The engine's spans and wait counters (``serve/tracing.py``), and the
stable names of its jitted programs, on a tiny model on the CPU.

A trace reduction charges device-idle time to the ``serve.*`` span that
covers it and reads the programs by their HLO module names, so both are
pinned here: each ``span_n.*`` counter equals the events the profiler
recorded, every span nests inside one ``serve.step``, an iteration opens a
bounded number of spans whatever its slot count, and every program in
``engine.PROGRAMS`` compiles to the module ``jit_<name>``.
"""
import dataclasses
import glob
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import mixed_requests, small_pool, tiny

from repro.models import transformer as tf
from repro.serve import PagedServer, Request, engine as engmod

K = 2          # speculation depth of the speculative engine


@pytest.fixture(scope="module")
def model():
    cfg = tiny("llama2-7b")
    return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(model, spec: bool, **pool) -> PagedServer:
    cfg, params = model
    if spec:
        return PagedServer(cfg, params, small_pool(**pool),
                           draft_params=params, speculate=K)
    return PagedServer(cfg, params, small_pool(**pool))


def staggered(cfg):
    """The churn workload with arrivals spread over a few milliseconds,
    so queue wait is not zero by construction."""
    return [dataclasses.replace(r, arrival=0.002 * r.rid)
            for r in mixed_requests(cfg)]


def span_counts(stats) -> dict:
    return {k[len("span_n."):]: v for k, v in stats.items()
            if k.startswith("span_n.")}


def test_programs_lower_to_stable_module_names(model):
    eng = make_engine(model, spec=True)
    s, w = eng.pool.max_slots, eng.table_width
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    b = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bool_)    # noqa: E731
    rows = (b(s), i32(s, w), i32(s))
    args = {
        "_step": (eng.params, eng.caches, i32(s, 1), i32(s), *rows),
        "_draft_step": (eng.draft_params, eng.draft_caches, i32(s, 1),
                        i32(s), *rows),
        "_chunk": (eng.params, eng.caches, i32(1, 4), i32(), i32(), i32(w),
                   i32()),
        "_verify": (eng.params, eng.caches, i32(s, K + 1), i32(s), *rows,
                    b(s, K + 1)),
        "_catchup": (eng.draft_params, eng.draft_caches, i32(s, 2), i32(s),
                     *rows, b(s, 2)),
        "_cow": (eng.caches, i32(), i32()),
    }
    assert set(engmod.PROGRAMS) == set(args)
    assert {"_step", "_chunk"} <= set(engmod.PROGRAMS)
    for name in engmod.PROGRAMS:
        with eng._kernel_scope():
            hlo = getattr(eng, name).lower(*args[name]).as_text(
                dialect="hlo")
        module = re.match(r"HloModule (\S+?),", hlo).group(1)
        assert module == f"jit_{name}", (name, module)


@pytest.mark.parametrize("spec", [False, True], ids=["decode", "spec"])
def test_counters_match_the_work_done(model, spec):
    cfg, _ = model
    eng = make_engine(model, spec)
    reqs = staggered(cfg)
    res = eng.run(reqs)
    st = eng.stats
    n = span_counts(st)
    phase, steps_key = ("spec", "spec_rounds") if spec else (
        "decode", "decode_steps")
    assert n[phase] == st[steps_key] == len(st["occupancy"])
    assert n["prefill"] == st["prefill_chunks"]
    assert n["admit"] == n["step"]
    # one pull and one sample per completed prompt, never per chunk
    assert n["prefill.pull"] == n["prefill.sample"] == len(reqs)
    for name in n:
        assert st[f"span_s.{name}"] >= 0.0
    assert st["admitted"] == st["prefill_started"] == len(res) == len(reqs)
    assert st["queue_wait_s"] == pytest.approx(
        sum(r.t_admit - q.arrival for q, r in
            ((q, res[q.rid]) for q in reqs)))
    assert st["queue_wait_s"] >= 0.0 and st["prefill_wait_s"] >= 0.0
    # two slots for five requests whose prompts take up to four chunks:
    # some decode steps run beside a request still prefilling
    assert 0 < st["prefill_slot_steps"] <= st[steps_key] * eng.pool.max_slots
    assert "decode_gap_s" not in st


def test_prefill_slots_and_occupancy_fill_the_slots(model):
    """Slots decoding plus slots prefilling never exceed the slot count,
    step by step, and the busy churn workload fills them at times."""
    cfg, _ = model
    eng = make_engine(model, spec=False)
    for r in mixed_requests(cfg):
        eng.submit(r)
    full = 0
    while eng.poll():
        steps0 = eng.stats.get("decode_steps", 0)
        pre0 = eng.stats.get("prefill_slot_steps", 0)
        eng.step()
        if eng.stats.get("decode_steps", 0) > steps0:
            held = (eng.stats["prefill_slot_steps"] - pre0
                    + eng.stats["occupancy"][-1] * eng.pool.max_slots)
            assert held <= eng.pool.max_slots
            full += held == eng.pool.max_slots
    assert full > 0


def test_queue_wait_counts_first_admission_only(model):
    """A preempted request's replay is admitted again but waited once."""
    cfg, _ = model
    eng = make_engine(model, spec=False)
    r0, r1 = mixed_requests(cfg)[:2]
    eng.submit(r0)
    eng.submit(r1)
    eng.step()
    assert eng.stats["admitted"] == 2
    eng.submit(eng.preempt(r0.rid))
    while eng.poll():
        eng.step()
    assert eng.stats["replays"] == 1
    assert eng.stats["admitted"] == eng.stats["prefill_started"] == 2


def serve_events(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every ``serve.*`` event of the
    newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.parametrize("spec", [False, True], ids=["decode", "spec"])
def test_trace_spans_nest_in_steps_and_match_counters(model, spec, tmp_path):
    cfg, _ = model
    eng = make_engine(model, spec)
    eng.run(staggered(cfg)[:2])        # compile outside the trace
    before = span_counts(eng.stats)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(staggered(cfg))
    finally:
        jax.profiler.stop_trace()
    events = serve_events(tmp_path)
    counted = {k: v - before.get(k, 0)
               for k, v in span_counts(eng.stats).items()}
    seen: dict = {}
    for name, *_ in events:
        seen[name[len("serve."):]] = seen.get(name[len("serve."):], 0) + 1
    assert seen == {k: v for k, v in counted.items() if v}
    steps = sorted((s, e) for name, s, e, _ in events
                   if name == "serve.step")
    for name, s, e, meta in events:
        if name == "serve.step":
            continue
        assert any(s0 <= s and e <= e1 for s0, e1 in steps), name
        if name == "serve.prefill":
            assert "rid" in meta and meta["tokens"] >= 1
        if name in ("serve.decode", "serve.spec"):
            assert 1 <= meta["slots"] <= eng.pool.max_slots


@pytest.mark.parametrize("slots", [2, 8])
def test_span_count_does_not_grow_with_slots(model, slots):
    """At most 12 spans an iteration and 2 an admission: no span is
    opened per slot or per token.  Eight one-chunk prompts fill up to
    eight slots, so a span per slot would break the bound."""
    cfg, _ = model
    eng = make_engine(model, spec=False, max_slots=slots)
    rng = np.random.default_rng(0)
    eng.run([Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4).astype(
        np.int32), max_new=12) for i in range(8)])
    n = span_counts(eng.stats)
    assert sum(n.values()) <= 12 * n["step"] + 2 * eng.stats["admitted"]
    assert n["decode"] == eng.stats["decode_steps"]
