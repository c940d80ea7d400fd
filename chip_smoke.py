#!/usr/bin/env python3
"""Chip smoke test: serve a RaanA-quantized qwen3-0.6b on one TPU.

Drives the serving path once, in one process, through the code that
``python -m repro.launch.serve`` runs, at qwen3-0.6b's published widths
(28 layers, d_model 1024, 16/8 heads of 128, d_ff 3072, vocab 151936) with
random weights made from ``--seed``:

  (a) device check: exits non-zero, before any work, unless JAX's first
      device is a TPU;
  (b) init, calibrate (zero-shot sentence) and RaanA-quantize to 3.3
      average bits (``serve.load_model``);
  (c) serve 8 requests through ``PagedServer`` with the TPU defaults —
      fused RHT+qmatmul kernel, paged-attention kernel, prefix cache on —
      64-128 prompt tokens sharing a 32-token prefix, 16 new tokens each,
      4 slots, so the second wave hits the prefix cache;
  (d) check that both dispatchers chose the compiled kernels
      (``interpret=False``) and that the compiled decode step holds one
      ``tpu_custom_call`` per quantized linear and per attention layer;
  (e) serve the same requests on the kernels and with both dispatchers
      forced to the jnp reference, both with f32 contractions, and compare
      the prefill-then-decode logits (every step whose history agrees)
      and the greedy tokens;
  (f) boot the HTTP front door in-process on a thread (port 0) over the
      same engine and stream two new requests through the stdlib client.

Logit tolerance: max |logit - ref| <= LOGIT_TOL * max |ref logit|, with
both runs under ``jax.default_matmul_precision("highest")``, so the
reference's XLA dots and the kernels' Mosaic dots both contract in f32 and
differ only in summation order.  At XLA's default TPU precision (one bf16
MXU pass, rounding 2^-9 per operand) the reference alone drifts from the
kernels by about 4e-2 of the logit range over the 28 layers of random
weights (measured on a TPU v5e), which would hide a real fault.  f32
rounding is 2^-24, so the same amplification leaves about 1e-5; 1e-3
leaves room for that and still fails any wrong unpack, rotation or
attention mask, which moves logits by O(1) of their range.  The
default-precision kernel run of (c) is compared with the f32 one and
printed, not checked.  Greedy tokens are reported, not required to agree:
random weights give near-tied logits, where rounding can flip an argmax.

``--tp 4`` runs only the tensor-parallel check: the same quantized model
and requests on a (1, 4) ("data", "model") mesh, against TP=1 on the first
device in the same process, both with f32 contractions, with greedy tokens
and logits compared.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failed phase exits non-zero without it.

  python chip_smoke.py [--seed 0]
  python chip_smoke.py --tp 4
"""
from __future__ import annotations

import argparse
import collections
import json
import re
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SERVE_ARGS = ["--arch", "qwen3-0.6b", "--avg-bits", "3.3", "--requests", "8",
              "--prompt-len", "128", "--gen", "16", "--slots", "4"]
SHARED = 32                                   # shared prompt prefix
PROMPT_LENS = (64, 96, 128, 64, 96, 128, 64, 128)
LOGIT_TOL = 1e-3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeError(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


class CompileMeter:
    """Seconds in XLA's backend compiler, and persistent compilation cache
    hits and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.seconds += secs

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            self.events[name.rsplit("/", 1)[1]] += 1


def require_devices(count: int) -> dict:
    """(a): the device JAX reports, or exit before any work."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[a] devices: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev['platform']}")
    if dev["count"] < count:
        raise SystemExit(f"chip_smoke: needs {count} chips, found "
                         f"{dev['count']}")
    return dev


def make_requests(vocab: int, seed: int):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, SHARED)
    return [Request(rid=i, prompt=np.concatenate(
                [shared, rng.integers(0, vocab, n - SHARED)]).astype(np.int32),
                    max_new=16)
            for i, n in enumerate(PROMPT_LENS)]


def serve_requests(engine, reqs, meter):
    """engine.run(reqs), recording every logits row the engine samples
    from; returns (results, logits by (rid, step), wall s, compile s)."""
    import numpy as np
    logits = {}
    sample = engine._sample

    def record(lg, rid, step):
        logits[rid, step] = np.array(lg, np.float32)
        return sample(lg, rid, step)

    engine._sample = record
    c0, t0 = meter.seconds, time.perf_counter()
    try:
        results = engine.run(reqs)
    finally:
        del engine._sample
    return results, logits, time.perf_counter() - t0, meter.seconds - c0


def compare(name, res_a, lg_a, res_b, lg_b):
    """Logits difference over every step both runs took from the same
    history, and greedy token agreement; returns the relative difference."""
    import numpy as np
    max_abs, max_ref, same_tok, n_tok, same_req = 0.0, 0.0, 0, 0, 0
    for rid, ra in res_a.items():
        ta, tb = ra.tokens, res_b[rid].tokens
        n = min(len(ta), len(tb))
        eq = ta[:n] == tb[:n]
        first_diff = int(np.argmin(eq)) if not eq.all() else n - 1
        for step in range(first_diff + 1):
            a, b = lg_a[rid, step], lg_b[rid, step]
            max_abs = max(max_abs, float(np.abs(a - b).max()))
            max_ref = max(max_ref, float(np.abs(b).max()))
        same_tok += int(eq.sum())
        n_tok += max(len(ta), len(tb))
        same_req += int(eq.all() and len(ta) == len(tb))
    rel = max_abs / max(max_ref, 1e-30)
    print(f"{name}: logits max|diff|={max_abs!r} max|ref|={max_ref!r} "
          f"rel={rel!r}; greedy tokens identical "
          f"{same_tok}/{n_tok}, requests identical {same_req}/{len(res_a)}",
          flush=True)
    return rel


def kernel_custom_calls(engine) -> collections.Counter:
    """Pallas kernels (by name) in the compiled decode step's HLO."""
    import jax.numpy as jnp
    s, mb = engine.pool.max_slots, engine.table_width
    args = (engine.params, engine.caches, jnp.zeros((s, 1), jnp.int32),
            jnp.zeros(s, jnp.int32), jnp.zeros(s, bool),
            jnp.zeros((s, mb), jnp.int32), jnp.ones(s, jnp.int32))
    with engine._kernel_scope():
        hlo = engine._step.lower(*args).compile().as_text()
    found = collections.Counter()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)
            found[m.group(1) if m else "unnamed"] += 1
    return found


def n_quantized(params) -> int:
    import jax
    from repro.core.qlinear import QuantizedLinear
    is_q = lambda x: isinstance(x, QuantizedLinear)   # noqa: E731
    return sum(map(is_q, jax.tree.leaves(params, is_leaf=is_q)))


def http_phase(engine, reqs) -> None:
    """(f): the front door in-process, two requests streamed over SSE."""
    from repro.serve.frontdoor import FrontDoor, SchedConfig
    from repro.serve.frontdoor.client import stream_generate
    door = FrontDoor(engine, SchedConfig(), port=0)
    thread = threading.Thread(target=door.serve_forever, daemon=True,
                              name="frontdoor")
    thread.start()
    check(door.listening.wait(60), "front door did not start listening")
    try:
        for r in reqs[:2]:
            t0 = time.perf_counter()
            events = list(stream_generate("127.0.0.1", door.port,
                                          tokens=r.prompt.tolist(),
                                          max_new=r.max_new, timeout=600))
            toks = [d["token"] for e, d in events if e == "token"]
            done = [d for e, d in events if e == "done"]
            check(len(done) == 1 and done[0]["tokens"] == toks
                  and len(toks) == r.max_new,
                  f"bad SSE stream for request {r.rid}: {events[-3:]}")
            print(f"[f] streamed request {r.rid}: {len(toks)} token events "
                  f"+ done in {time.perf_counter() - t0!r}s, ttft "
                  f"{done[0]['ttft_s']!r}s", flush=True)
    finally:
        door.shutdown()
        thread.join(60)
    check(not thread.is_alive(), "front door did not shut down")


def single_chip(sargs, cfg, params, seed, meter) -> None:
    import jax
    from repro.kernels import default_interpret
    from repro.kernels.paged_attention import ops as pops
    from repro.kernels.qmatmul import ops as qops
    from repro.launch import serve

    # (c) the TPU defaults
    engine = serve.build_engine(sargs, cfg, params)
    results, logits, wall, comp = serve_requests(
        engine, make_requests(cfg.vocab, seed), meter)
    n_tok = sum(len(r.tokens) for r in results.values())
    with engine._kernel_scope():
        paths = {"qmatmul": qops.resolve_path(),
                 "paged_attention": "pallas" if pops.kernel_enabled()
                 else "ref"}
    st = engine.stats
    print(f"[c] served {len(results)} requests, {n_tok} tokens: wall "
          f"{wall!r}s = XLA compile {comp!r}s + the rest "
          f"{wall - comp!r}s; "
          f"decode_trace_count={engine.decode_trace_count}, paths={paths}, "
          f"fused={engine.fused}, prefix_hit_rate="
          f"{st.get('prefix_hit_rate')!r}, decode_steps="
          f"{st.get('decode_steps')}", flush=True)
    check(n_tok == 16 * len(PROMPT_LENS), f"served {n_tok} tokens")
    check(engine.decode_trace_count == 1,
          f"decode traced {engine.decode_trace_count} times")
    check(engine.fused, "the RHT is not fused into the qmatmul kernel")

    # (d) compiled kernels, not the interpreter, in the decode step
    interpret = default_interpret()
    found = kernel_custom_calls(engine)
    want = {"rht_qmatmul": n_quantized(engine.params),
            "paged_attention": cfg.n_layers}
    print(f"[d] dispatch {paths}, interpret={interpret}; decode-step "
          f"tpu_custom_calls {dict(found)} (want {want})", flush=True)
    check(all(p == "pallas" for p in paths.values()) and not interpret,
          f"dispatch is not compiled pallas: {paths}, interpret={interpret}")
    check(all(found[k] == v for k, v in want.items()),
          f"decode HLO kernels {dict(found)} != {want}")

    # (e) the same requests on the kernels and on the jnp reference paths,
    # both contracting in f32
    with jax.default_matmul_precision("highest"):
        hi_engine = serve.build_engine(sargs, cfg, params)
        hi_results, hi_logits, hwall, hcomp = serve_requests(
            hi_engine, make_requests(cfg.vocab, seed), meter)
        del hi_engine
        qops.set_forced_path("ref")
        pops.set_forced_path("ref")
        try:
            ref_engine = serve.build_engine(sargs, cfg, params)
            ref_results, ref_logits, rwall, rcomp = serve_requests(
                ref_engine, make_requests(cfg.vocab, seed), meter)
        finally:
            qops.set_forced_path(None)
            pops.set_forced_path(None)
        del ref_engine
    print(f"[e] f32 contractions: kernel run wall {hwall!r}s, compile "
          f"{hcomp!r}s; reference run wall {rwall!r}s, compile {rcomp!r}s",
          flush=True)
    compare("[e] kernels, default vs f32 contractions (not checked)",
            results, logits, hi_results, hi_logits)
    rel = compare("[e] kernels vs reference, f32 contractions", hi_results,
                  hi_logits, ref_results, ref_logits)
    check(rel <= LOGIT_TOL, f"logits differ by {rel!r} of their range "
                            f"(tol {LOGIT_TOL})")

    # (f) the streaming front door over the same engine; prompts it has not
    # cached yet, so prefill runs the same chunk shapes as in (c)
    http_phase(engine, make_requests(cfg.vocab, seed + 1))
    jax.block_until_ready(engine.caches)


def tensor_parallel(sargs, cfg, params, seed, meter, tp: int) -> None:
    import jax
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(tp=tp)
    with jax.default_matmul_precision("highest"):
        engine = serve.build_engine(sargs, cfg, params, mesh=mesh)
        arena = engine.caches[0]["k"]
        shards = {str(s.device): s.data.shape
                  for s in arena.addressable_shards}
        print(f"[tp] mesh {dict(mesh.shape)}, plan "
              f"{engine.tp_plan.asdict()}, KV arena shards {shards}",
              flush=True)
        check(len(shards) == tp and all(sh[2] == cfg.n_kv // tp
                                        for sh in shards.values()),
              "KV arena is not split over the chips by head")
        res_tp, lg_tp, wall, comp = serve_requests(
            engine, make_requests(cfg.vocab, seed), meter)
        print(f"[tp] TP={tp}: wall {wall!r}s, compile {comp!r}s, "
              f"decode_trace_count={engine.decode_trace_count}", flush=True)
        del engine
        one = serve.build_engine(sargs, cfg, params)
        res_1, lg_1, wall1, comp1 = serve_requests(
            one, make_requests(cfg.vocab, seed), meter)
    print(f"[tp] TP=1 on {jax.devices()[0]}: wall {wall1!r}s, compile "
          f"{comp1!r}s", flush=True)
    rel = compare(f"[tp] TP={tp} vs TP=1, f32 contractions", res_tp, lg_tp,
                  res_1, lg_1)
    check(rel <= LOGIT_TOL, f"TP logits differ by {rel!r} of their range "
                            f"(tol {LOGIT_TOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and prompts are made from this seed")
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel check on this many "
                         "chips (the one-chip phases are skipped)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.jaxcache import use_compile_cache
    cache_dir = use_compile_cache()
    dev = require_devices(args.tp)
    meter = CompileMeter()
    print(f"compile cache: {cache_dir}", flush=True)

    from repro.launch import serve
    sargs = serve.parse_args(SERVE_ARGS)
    t0 = time.perf_counter()
    cfg, params, _, rep = serve.load_model(sargs, seed=args.seed)
    print(f"[b] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv}x{cfg.hd}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}; achieved {rep.avg_bits!r} avg bits (asked "
          f"{rep.requested_avg_bits}); quantization {rep.wall_time_s!r}s, "
          f"init+calibration+quantization {time.perf_counter() - t0!r}s",
          flush=True)
    check(abs(rep.avg_bits - 3.3) < 0.1, f"avg bits {rep.avg_bits}")

    if args.tp > 1:
        tensor_parallel(sargs, cfg, params, args.seed, meter, args.tp)
    else:
        single_chip(sargs, cfg, params, args.seed, meter)
    print(f"XLA compile in all phases: {meter.seconds!r}s; persistent "
          f"compilation cache {dict(meter.events)}", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
