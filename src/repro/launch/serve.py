"""Serving CLI: continuous-batching paged engine (default) or the lockstep
baseline, with optional RaanA-quantized weights — the deployment artifact of
the paper.

The paged engine (repro.serve) runs a block-arena KV pool with per-request
block tables: requests are admitted against free blocks, prompts prefill in
chunks interleaved with decode, and completed requests free their slot
immediately.  Shared prompt prefixes are served from the content-addressed
prefix cache (``--no-prefix-cache`` for a cold pool A/B; the printed
``prefix_hit_rate`` is the fraction of prompt tokens whose prefill was
skipped), and ``--kv-dtype bf16`` halves the KV arena bytes.  ``--lockstep``
keeps the legacy ``BatchedServer`` behavior (aligned prefill, whole-batch
decode until the last request finishes) as the A/B baseline.  ``--unfused``
restores the two-kernel RHT+qmatmul composition (rotated activations
round-trip through HBM) for A/B measurement, and ``--paged-kernel`` /
``--no-paged-kernel`` pins the decode attention read to the Pallas
flash-decode kernel over the block arena vs the dense gather path
(DESIGN.md §10; unset, the backend decides).  ``--speculate K`` turns on
self-speculative decoding: the same weights are quantized a second time at
``--draft-bits`` (sharing the calibration pass and Hadamard rotation with
the target quantization) and the engine runs draft-propose/target-verify
rounds — greedy outputs stay token-identical, and the printed
``acceptance_rate`` tracks how many draft tokens survive verification.
``--tp N`` serves tensor-parallel over a ``(data, model)`` mesh
(DESIGN.md §11): quantized columns, attention heads, and the KV arena
shard over N chips and greedy outputs stay token-identical to ``--tp 1``;
on CPU, force devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

``--serve`` switches from the one-shot batch run to the streaming front
door (DESIGN.md §12): an HTTP server on ``--port`` exposing
``POST /v1/generate`` with per-token SSE, per-tenant priority admission
with weighted fair sharing (``--max-tenant-share`` caps one tenant's slot
fraction), drop-and-replay preemption, and — with ``--slo-p95-ms`` set —
an SLO controller that throttles chunked-prefill admission when the
decode-gap p95 exceeds the target.  The engine knobs above (slots, block
size, quantization, speculation, tp) all apply to the served engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --tiny \
      --avg-bits 3.3 --requests 8 --gen 32

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --tiny \
      --avg-bits 4.0 --speculate 3 --draft-bits 2.2 --requests 4 --gen 16

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --tiny \
      --serve --port 8080 --slo-p95-ms 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, get_tiny
from repro.core import calibrate as cal
from repro.core import pipeline as pipe
from repro.data import ByteTokenizer
from repro.kernels.paged_attention import ops as pops
from repro.kernels.qmatmul import ops as qops
from repro.launch.jaxcache import use_compile_cache
from repro.models import decode as decmod
from repro.models import transformer as tf
from repro.serve import PagedServer, PoolConfig, Request


class BatchedServer:
    """Minimal batched LM server: aligned prefill + lockstep decode.

    All requests prefill together and decode in lockstep until the batch's
    last request finishes — the baseline the paged engine is measured
    against.  Greedy or temperature sampling; quantized models route every
    linear through Alg. 3 (QuantizedLinear.apply) transparently.
    """

    def __init__(self, cfg, params, max_context: int = 512):
        self.cfg = cfg
        self.params = params
        self.max_context = max_context
        self._decode = jax.jit(
            lambda p, c, t, pos: decmod.decode_step(cfg, p, c, t, pos,
                                                    scan=False))

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 temperature: float = 0.0, key=None):
        """prompts (B, S) int32 -> (B, n_tokens) int32."""
        b, s = prompts.shape
        logits, caches, pos = decmod.prefill(
            self.cfg, self.params, jnp.asarray(prompts),
            context=self.max_context, scan=False)
        last = logits[:, -1, :]
        out = []
        key = key if key is not None else jax.random.PRNGKey(0)
        for t in range(n_tokens):
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, last / temperature, axis=-1)
            else:
                tok = jnp.argmax(last, axis=-1)
            out.append(tok)
            last, caches = self._decode(self.params, caches, tok[:, None],
                                        jnp.int32(s + t))
        return np.stack([np.asarray(t) for t in out], axis=1)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--avg-bits", type=float, default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--lockstep", action="store_true",
                    help="legacy whole-batch server (A/B baseline)")
    ap.add_argument("--unfused", action="store_true",
                    help="disable RHT+qmatmul fusion (A/B baseline)")
    ap.add_argument("--slots", type=int, default=4,
                    help="paged engine: concurrent request slots")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="paged engine: prompt tokens per scheduler turn")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="content-addressed KV prefix reuse (paged engine; "
                         "auto-bypassed for windowed/recurrent archs)")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16"], default="f32",
                    help="paged engine: KV arena + slot-state dtype")
    ap.add_argument("--paged-kernel", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="route paged attention through the Pallas "
                         "flash-decode kernel over the block arena "
                         "(interpret-mode off TPU); --no-paged-kernel "
                         "forces the dense gather path; default lets the "
                         "backend decide (kernel on TPU)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="self-speculative decoding: draft K tokens per "
                         "round from a low-bit quantization of the same "
                         "weights, verify them in one target step (paged "
                         "engine; attention archs only — recurrent/MLA "
                         "bypass)")
    ap.add_argument("--draft-bits", type=float, default=2.2,
                    help="average bit budget for the speculative draft "
                         "quantization (used when --speculate > 0)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: serve over a "
                         "(data, model) mesh with this many chips on the "
                         "model axis (paged engine; must divide the device "
                         "count — on CPU force devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--serve", action="store_true",
                    help="boot the streaming HTTP/SSE front door on --port "
                         "instead of the one-shot batch run (paged engine; "
                         "POST /v1/generate, GET /healthz, GET /v1/stats); "
                         "--prompt-len + --gen size the pool's max context")
    ap.add_argument("--port", type=int, default=8080,
                    help="front door listen port (0 binds an ephemeral "
                         "port; the chosen one is printed at boot)")
    ap.add_argument("--slo-p95-ms", type=float, default=None,
                    help="front door: decode-gap p95 target in ms — past "
                         "it the scheduler throttles chunked-prefill "
                         "admission until p95 recovers (default: "
                         "controller off)")
    ap.add_argument("--max-tenant-share", type=float, default=1.0,
                    help="front door: max fraction of engine slots one "
                         "tenant may hold while other tenants wait "
                         "(default 1.0 = uncapped)")
    args = ap.parse_args(argv)
    if args.speculate and args.lockstep:
        ap.error("--speculate needs the paged engine (drop --lockstep)")
    if args.tp > 1 and args.lockstep:
        ap.error("--tp needs the paged engine (drop --lockstep)")
    if args.serve and args.lockstep:
        ap.error("--serve needs the paged engine (drop --lockstep)")
    return args


def load_model(args, seed: int = 0):
    """Random-init the ``--arch`` model from ``seed`` and, with
    ``--avg-bits`` / ``--speculate``, calibrate and RaanA-quantize it.
    Returns ``(cfg, params, draft_params, report)``; ``report`` is the
    target's ``QuantReport`` (None for an fp32 target)."""
    cfg = get_tiny(args.arch) if args.tiny else get_config(args.arch)
    params = tf.init_params(cfg, jax.random.PRNGKey(seed))
    draft_params, rep = None, None
    if args.avg_bits or args.speculate:
        stats_msg = (f"{args.avg_bits} avg bits" if args.avg_bits
                     else "fp32 target")
        print(f"calibrating + quantizing ({stats_msg}"
              + (f", {args.draft_bits}-bit draft" if args.speculate else "")
              + ") ...")
        toks = cal.zero_shot_tokens(cfg.vocab, 256)
        stats = cal.calibrate(
            lambda p, b, ctx: tf.loss_fn(cfg, p, b, ctx=ctx, scan=False),
            params, [{"tokens": jnp.asarray(toks)}])
        qkey = jax.random.PRNGKey(seed + 1)
        if args.avg_bits and args.speculate:
            params, rep, draft_params, drep = pipe.quantize_model_dual(
                cfg, params, stats, args.avg_bits, args.draft_bits, qkey)
            print(f"quantized {rep.n_layers} layers, achieved "
                  f"{rep.avg_bits:.3f} target / {drep.avg_bits:.3f} draft "
                  f"bits in {rep.wall_time_s + drep.wall_time_s:.1f}s")
        elif args.avg_bits:
            params, rep = pipe.quantize_model(cfg, params, stats,
                                              args.avg_bits, qkey)
            print(f"quantized {rep.n_layers} layers, achieved "
                  f"{rep.avg_bits:.3f} bits in {rep.wall_time_s:.1f}s")
        else:   # fp32 target, quantized draft
            draft_params, drep = pipe.quantize_model(
                cfg, params, stats, args.draft_bits, qkey)
            print(f"quantized draft: {drep.avg_bits:.3f} bits in "
                  f"{drep.wall_time_s:.1f}s")
    return cfg, params, draft_params, rep


def build_engine(args, cfg, params, draft_params=None, mesh=None):
    """The paged engine the CLI serves with (``mesh`` overrides the
    ``--tp`` host mesh)."""
    pool = PoolConfig(max_slots=args.slots, block_size=args.block_size,
                      max_context=args.prompt_len + args.gen,
                      prefill_chunk=args.prefill_chunk,
                      prefix_cache=args.prefix_cache,
                      kv_dtype=(jnp.bfloat16 if args.kv_dtype == "bf16"
                                else jnp.float32))
    if mesh is None and args.tp > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(tp=args.tp)
    return PagedServer(cfg, params, pool, fused=not args.unfused,
                       paged_kernel=args.paged_kernel,
                       draft_params=draft_params,
                       speculate=args.speculate, mesh=mesh)


def main(argv=None):
    use_compile_cache()
    args = parse_args(argv)
    cfg, params, draft_params, _ = load_model(args)

    tok = ByteTokenizer(cfg.vocab)
    prompt = tok.encode("the quick brown fox " * 8)[: args.prompt_len]
    t0 = time.time()
    if args.lockstep:
        with qops.fusion(not args.unfused):
            server = BatchedServer(cfg, params,
                                   max_context=args.prompt_len + args.gen)
            prompts = np.stack([prompt for _ in range(args.requests)])
            out = server.generate(prompts, args.gen)
        sample = out[0]
        extra = "lockstep"
    else:
        engine = build_engine(args, cfg, params, draft_params)
        if args.serve:
            from repro.serve.frontdoor import FrontDoor, SchedConfig
            door = FrontDoor(
                engine,
                SchedConfig(slo_p95_ms=args.slo_p95_ms,
                            max_tenant_share=args.max_tenant_share),
                port=args.port)
            door.serve_forever()
            return
        results = engine.run([Request(rid=i, prompt=np.asarray(prompt),
                                      max_new=args.gen)
                              for i in range(args.requests)])
        sample = results[0].tokens
        with pops.paged_kernel(args.paged_kernel):
            attn_path = "kernel" if pops.kernel_enabled() else "gather"
        m = engine.mesh.shape
        extra = (f"paged, occupancy={engine.stats['mean_occupancy']:.2f}, "
                 f"decode_traces={engine.decode_trace_count}, "
                 f"attn={attn_path}, "
                 f"mesh={m['data']}x{m['model']}, tp={engine.tp}")
        if engine.speculate:
            extra += (f", speculate={engine.speculate}, acceptance_rate="
                      f"{engine.stats['acceptance_rate']:.2f}")
        if engine.prefix_cache is not None:
            extra += (f", prefix_hit_rate="
                      f"{engine.stats['prefix_hit_rate']:.2f}, "
                      f"prefill_tokens_saved="
                      f"{engine.stats.get('prefill_tokens_saved', 0)}")
    dt = time.time() - t0
    path = "unfused" if args.unfused else "fused"
    print(f"served {args.requests} requests x {args.gen} tokens in {dt:.2f}s "
          f"({args.requests*args.gen/dt:.1f} tok/s, {path} decode path, "
          f"{extra})")
    print("sample:", tok.decode(np.asarray(sample))[:80])


if __name__ == "__main__":
    main()
