"""Continuous-batching LM serving engine over the paged KV-cache pool.

Scheduler loop (one iteration): admit queued requests while slots and blocks
are free, run ONE prompt chunk for the oldest mid-prefill request, then run
ONE decode step over the whole slot set.  Chunked prefill therefore
interleaves with decode instead of stalling it, and a request that hits EOS
or its token budget frees its slot and blocks immediately, so queued
requests join mid-flight — nobody waits for a batch to drain (the lockstep
failure mode ``launch/serve.BatchedServer`` keeps around as the A/B
baseline).

The decode step is jitted ONCE per engine: batch-composition churn only
changes the *contents* of (tokens, pos, active, block_tables, ring_cap)
arrays, never their shapes, so quantized weights stay resident and decode
occupancy is limited by traffic, not recompilation
(``decode_trace_count`` is asserted == 1 in tests/test_paged_engine.py).

Admission consults the content-addressed prefix cache (DESIGN.md §8): the
longest cached prefix of the prompt is served straight from the pool
(refcounts bumped, chunked prefill starts at the first uncached token, a
mid-block match is cloned copy-on-write), and completed requests *release*
their blocks — fully-written blocks stay cached on an LRU that is evicted
only under allocation pressure.  Pure-attention, non-windowed archs only;
ring-window blocks mutate in place and recurrent/MLA state is per-slot, so
those configs bypass the cache entirely.

Self-speculative decoding (DESIGN.md §9): with ``draft_params`` (a second,
aggressively low-bit quantization of the SAME weights — see
``core.pipeline.quantize_model_dual``) and ``speculate=k``, the decode
phase becomes draft-propose / target-verify: the draft decodes k tokens
autoregressively through its own KV arena (same block tables as the
target's, so prefix hits warm both), the target scores all k+1 positions in
one batched ``decode_verify_paged`` step, and the standard rejection-
sampling acceptance rule emits between 1 and k+1 tokens per round while
preserving the target distribution exactly (greedy mode is token-identical
to non-speculative decoding).  Attention archs only; recurrent/MLA archs
bypass speculation because their sequential per-slot state cannot absorb
rejected positions.

Tracing: every layer boundary of an iteration runs inside a
``tracing.span`` (``serve.step`` around ``admit`` (with ``cow``),
``prefill``, and ``decode`` or ``spec``, each of the last three split into
``.inputs``, ``.dispatch``, ``.pull`` and ``.sample``), whose host time and
count accumulate in ``stats["span_s.*"]`` / ``stats["span_n.*"]``.  Beside
them ``stats`` holds cumulative counters of the waits before a request's
first chunk: ``admitted`` / ``queue_wait_s`` (arrival to admission) and
``prefill_started`` / ``prefill_wait_s`` (admission to the first chunk),
and ``prefill_slot_steps`` (slots held by prefilling requests, summed over
decode steps).  ``PROGRAMS`` lists every jitted program by attribute; its
compiled module is ``jit_<name>`` (``jit__step``, ``jit__chunk``), the
names a trace reduction may rely on.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import ops as pops
from repro.kernels.qmatmul import ops as qops
from repro.models import decode as decmod
from repro.models.config import ModelConfig
from repro.runtime import tp as tpmod

from .pool import (BlockAllocator, PoolConfig, PrefixCache, init_pool_caches,
                   request_blocks)
from .tracing import span

# Every jitted program of a PagedServer, by attribute name.  Each compiles
# to an HLO module named ``jit_<name>``; a trace reduction may rely on
# these names, so they do not change.
PROGRAMS = ("_step", "_chunk", "_verify", "_draft_step", "_catchup", "_cow")


@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival`` is seconds after engine-clock
    start (workload simulation / HTTP arrival time); the engine will not
    admit it earlier.  ``tenant`` / ``priority`` / ``deadline`` are
    scheduling metadata the engine itself ignores — the front-door
    ``Scheduler`` (serve/frontdoor, DESIGN.md §12) orders admission and
    picks preemption victims by them.  ``on_token`` (if set) is called as
    ``on_token(rid, token, t)`` from the serving thread the moment each
    token is emitted — the streaming hook the SSE server bridges onto an
    asyncio queue; it must be cheap and must not raise."""
    rid: int
    prompt: np.ndarray               # (plen,) int32
    max_new: int
    eos: Optional[int] = None
    arrival: float = 0.0
    tenant: str = "default"
    priority: int = 0                # higher = more urgent
    deadline: Optional[float] = None  # engine-clock seconds (SLO metadata)
    on_token: Optional[Callable[[int, int, float], None]] = None


@dataclasses.dataclass
class RequestResult:
    """Completion record for one request: the generated tokens plus the
    admission / first-token / completion timestamps (engine-clock seconds)
    the serving benchmarks turn into latency percentiles.  ``ttft_s`` is
    time-to-first-token measured from the request's *arrival* (queueing
    included), ``token_times`` the engine-clock emission time of every
    generated token, and ``preemptions`` how many times the request was
    drop-and-replay preempted (its timestamps span incarnations: ``t_admit``
    / ``t_first`` are from the first, ``t_done`` from the last)."""
    rid: int
    tokens: np.ndarray               # generated tokens (<= max_new)
    t_admit: float                   # engine-clock seconds
    t_first: float                   # first generated token
    t_done: float
    ttft_s: float = 0.0              # t_first - arrival
    token_times: np.ndarray = None   # (len(tokens),) emission times
    preemptions: int = 0
    tenant: str = "default"


@dataclasses.dataclass
class _Replay:
    """Continuation state of a preempted request, keyed by rid until the
    scheduler resubmits it: the tokens already emitted (replayed as extra
    prompt) and the first-incarnation timestamps."""
    prior: list
    t_admit: float
    t_first: float
    token_times: list
    preemptions: int


@dataclasses.dataclass
class _InFlight:
    req: Request
    slot: int
    blocks: list
    bt_row: np.ndarray               # (MB,) int32 physical block ids
    ring_cap: int                    # tokens; ring for windowed archs
    served: np.ndarray = None        # prompt + replayed tokens actually fed
    filled: int = 0                  # served tokens prefilled so far
    out: list = dataclasses.field(default_factory=list)
    prior: list = dataclasses.field(default_factory=list)  # pre-preemption
    token_times: list = dataclasses.field(default_factory=list)
    preemptions: int = 0
    t_admit: float = 0.0
    t_first: float = 0.0
    awaits_chunk: bool = False       # first admission, no chunk run yet
    chain: object = None             # prefix-cache hash of last full block
    n_hashed: int = 0                # full blocks matched/registered so far
    draft_pos: int = 0               # draft-KV-valid positions (speculation)

    @property
    def n_done(self) -> int:
        """Tokens emitted across all incarnations (sampling step index)."""
        return len(self.prior) + len(self.out)


def speculative_accept(target_logits: np.ndarray, draft_logits: np.ndarray,
                       draft_tokens: np.ndarray, temperature: float,
                       rng: np.random.Generator):
    """Standard speculative-sampling acceptance rule for one slot's round.

    ``target_logits`` (k+1, V) are the target model's logits at the k+1
    verified positions (last accepted token + k draft tokens);
    ``draft_logits`` (k, V) are the logits each ``draft_tokens[i]`` was
    sampled from.  Greedy (``temperature <= 0``): accept ``d_i`` while it
    equals the target argmax at its position, emit the target argmax at the
    first mismatch, emit the bonus argmax after a full accept — every
    emitted token is a target argmax, so greedy speculation is
    token-identical to non-speculative decoding.  Sampling
    (``temperature > 0``): accept ``d_i`` with probability
    ``min(1, p_t(d_i) / p_d(d_i))``, on rejection sample from the residual
    ``normalize(max(p_t - p_d, 0))``, after a full accept sample the bonus
    from the target's last distribution — the marginal distribution of
    emitted tokens equals target-only sampling (Leviathan et al., 2023;
    pinned statistically in tests/test_speculative.py).  Returns
    ``(tokens, n_accepted)`` with ``len(tokens) == n_accepted + 1``.
    """
    k = len(draft_tokens)
    out: list[int] = []
    if temperature <= 0.0:
        for i in range(k):
            t_star = int(np.argmax(target_logits[i]))
            out.append(t_star)
            if int(draft_tokens[i]) != t_star:
                return out, i
        out.append(int(np.argmax(target_logits[k])))
        return out, k

    def dist(logits):
        z = logits.astype(np.float64) / temperature
        e = np.exp(z - z.max())
        return e / e.sum()

    for i in range(k):
        p_t, p_d = dist(target_logits[i]), dist(draft_logits[i])
        d = int(draft_tokens[i])
        if rng.random() < min(1.0, p_t[d] / max(p_d[d], 1e-300)):
            out.append(d)
            continue
        resid = np.maximum(p_t - p_d, 0.0)
        s = resid.sum()
        p = resid / s if s > 0.0 else p_t
        out.append(int(rng.choice(p.size, p=p)))
        return out, i
    p_t = dist(target_logits[k])
    out.append(int(rng.choice(p_t.size, p=p_t)))
    return out, k


class PagedServer:
    """Continuous-batching engine over the paged KV pool; greedy or
    temperature sampling.

    ``fused`` selects the RHT+qmatmul fusion for every traced function of
    this engine via the scoped ``qops.fusion`` context (fixed per engine —
    each jitted step is traced under it exactly once).  ``paged_kernel``
    likewise pins the attention read: True routes every paged attention
    (decode / catch-up / verify) through the Pallas flash-decode kernel
    over the block arena (interpret-mode off TPU), False through the dense
    gather reference, and None (default) lets the backend decide — kernel
    on TPU, gather elsewhere (DESIGN.md §10).  ``draft_params`` +
    ``speculate=k`` turn on self-speculative decoding (draft proposes k
    tokens, target verifies them in one batched step; see the module
    docstring and DESIGN.md §9); recurrent/MLA archs silently bypass
    speculation and run the plain decode loop.  Construct once per (model,
    PoolConfig) — all serving state (arenas, allocator, queues, stats)
    lives on the instance, and ``run`` drains a workload to completion.

    ``mesh`` (a ``("data", "model")`` mesh, e.g. from
    ``launch.mesh.make_host_mesh(tp=2)``) turns on tensor-parallel serving
    (DESIGN.md §11): params are column-shard-placed per ``runtime.tp``'s
    plan, the KV block arenas shard their head axis, and every jitted step
    runs inside one ``shard_map`` over the mesh.  Default is the trivial
    (1, 1) mesh — single-device serving is the TP=1 special case of the
    same code path, not a separate one.  Scheduler/allocator/prefix-cache
    state stays host-side and replicated regardless of TP degree.
    """

    def __init__(self, cfg: ModelConfig, params: dict,
                 pool: PoolConfig | None = None, *, fused: bool = True,
                 paged_kernel: bool | None = None,
                 temperature: float = 0.0, seed: int = 0,
                 draft_params: dict | None = None, speculate: int = 0,
                 mesh=None):
        if cfg.enc_dec:
            raise ValueError(
                "PagedServer does not support encoder-decoder archs")
        if speculate < 0:
            raise ValueError(f"speculate must be >= 0 (got {speculate})")
        if speculate and draft_params is None:
            raise ValueError("speculate > 0 requires draft_params "
                             "(see core.pipeline.quantize_model_dual)")
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else tpmod.default_mesh()
        self.tp = int(self.mesh.shape[tpmod.AXIS])
        self.tp_plan = tpmod.plan_for(cfg, self.tp)
        self.params, self._pspecs = tpmod.prepare_params(cfg, params,
                                                         self.mesh)
        self.pool = pool or PoolConfig()
        self.fused = fused
        self.paged_kernel = paged_kernel
        self.temperature = temperature
        self.seed = seed
        # Speculation needs KV that is addressable by absolute position so
        # rejected tokens can simply be overwritten; sequential per-slot
        # state (RWKV/RG-LRU/MLA latents) cannot roll back, so those archs
        # bypass and serve through the plain decode loop (DESIGN.md §9).
        self.speculating = bool(speculate) and all(
            mx == "attn" for mx in cfg.pattern)
        self.speculate = speculate if self.speculating else 0
        if self.speculating:
            self.draft_params, self._draft_pspecs = tpmod.prepare_params(
                cfg, draft_params, self.mesh)
        else:
            self.draft_params, self._draft_pspecs = None, None
        if self.speculating and self.pool.lookahead < speculate:
            # verify/draft steps write up to `speculate` positions past the
            # accepted frontier; reserve ring capacity so those writes can
            # never wrap onto live history (window or prompt)
            self.pool = dataclasses.replace(self.pool, lookahead=speculate)
        # KV arenas shard their head axis when the plan shards attention;
        # recurrent/MLA slot state replicates (runtime/tp.py).
        self.caches = init_pool_caches(cfg, params, self.pool)
        self._cspecs = tpmod.cache_spec_list(self.caches, self.mesh,
                                             self.tp_plan)
        self.caches = tpmod.place(self.caches, self._cspecs, self.mesh)
        if self.speculating:
            dc = init_pool_caches(cfg, draft_params, self.pool)
            self.draft_caches = tpmod.place(dc, self._cspecs, self.mesh)
        else:
            self.draft_caches = None
        # Prefix caching needs blocks that are immutable once written:
        # pure-attention archs without a sliding window.  Windowed archs
        # ring-reuse their blocks in place, and recurrent/MLA state lives in
        # per-slot arrays the cache can't name — both bypass.
        self.cacheable = (self.pool.prefix_cache and cfg.window is None
                          and all(mx == "attn" for mx in cfg.pattern))
        self.prefix_cache = (PrefixCache(self.pool.block_size)
                             if self.cacheable else None)
        self.allocator = BlockAllocator(self.pool.resolved_num_blocks(cfg),
                                        cache=self.prefix_cache)
        self.free_slots = list(range(self.pool.max_slots - 1, -1, -1))
        self.table_width = max(
            request_blocks(cfg, self.pool, self.pool.max_context), 1)
        self.has_attn = "attn" in cfg.pattern
        self.decode_trace_count = 0
        self.draft_trace_count = 0        # single-token draft steps
        self.catchup_trace_count = 0      # 2-token draft catch-up steps
        self.verify_trace_count = 0       # (k+1)-token target verify steps
        self.stats: dict = {}
        self._pending: collections.deque[Request] = collections.deque()
        self._prefilling: collections.deque[_InFlight] = collections.deque()
        self._active: dict[int, _InFlight] = {}
        self._replay: dict[int, _Replay] = {}
        self._t0: float | None = None
        self._last_decode_end: float | None = None
        # Gap between the ends of consecutive decode steps — the per-token
        # decode latency a request actually observes, inflated by whatever
        # (chunked prefill, admission work) the scheduler interleaves.  The
        # front-door SLO controller reads the tail of this window.
        self.decode_gaps: collections.deque = collections.deque(maxlen=2048)

        # Caches are donated: the pool buffers alias input->output instead of
        # being copied every step (same pattern as launch/dryrun.py).  jit's
        # own shape cache handles the few distinct prefill chunk lengths.
        # Every step runs inside ONE shard_map over the engine mesh
        # (runtime/tp.sharded_call): params/caches enter under their
        # placement specs, step arguments and logits replicate, and cache
        # in/out specs match so donation survives the wrapper.  The draft
        # steps get their own wrappers because the draft quantization has
        # its own param spec list.
        def _wrap(core, pspecs):
            return tpmod.sharded_call(core, self.mesh, pspecs, self._cspecs)

        step_core = _wrap(
            lambda p_, c_, *a: decmod.decode_step_paged(cfg, p_, c_, *a),
            self._pspecs)
        chunk_core = _wrap(
            lambda p_, c_, *a: decmod.prefill_chunk_paged(cfg, p_, c_, *a),
            self._pspecs)
        verify_core = _wrap(
            lambda p_, c_, *a: decmod.decode_verify_paged(cfg, p_, c_, *a),
            self._pspecs)
        if self.speculating:
            draft_step_core = _wrap(
                lambda p_, c_, *a: decmod.decode_step_paged(cfg, p_, c_, *a),
                self._draft_pspecs)
            draft_verify_core = _wrap(
                lambda p_, c_, *a: decmod.decode_verify_paged(cfg, p_, c_,
                                                              *a),
                self._draft_pspecs)

        def _step(params_, caches, tokens, pos, active, bts, ring):
            self.decode_trace_count += 1      # trace-time side effect only
            return step_core(params_, caches, tokens, pos, active, bts, ring)

        def _draft_step(params_, caches, tokens, pos, active, bts, ring):
            self.draft_trace_count += 1       # trace-time side effect only
            return draft_step_core(params_, caches, tokens, pos, active,
                                   bts, ring)

        def _chunk(params_, caches, toks, pos0, slot, bt, ring):
            return chunk_core(params_, caches, toks, pos0, slot, bt, ring)

        def _verify(params_, caches, tokens, pos0, active, bts, ring, wmask):
            self.verify_trace_count += 1      # trace-time side effect only
            return verify_core(params_, caches, tokens, pos0, active, bts,
                               ring, wmask)

        def _catchup(params_, caches, tokens, pos0, active, bts, ring, wmask):
            self.catchup_trace_count += 1     # trace-time side effect only
            return draft_verify_core(params_, caches, tokens, pos0, active,
                                     bts, ring, wmask)

        def _cow_core(caches, src, dst):
            # clone one physical block's KV across every layer arena
            return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), caches)

        cow_op = tpmod.sharded_cache_op(_cow_core, self.mesh, self._cspecs)

        def _cow(caches, src, dst):
            return cow_op(caches, src, dst)

        self._step = jax.jit(_step, donate_argnums=(1,))
        self._draft_step = jax.jit(_draft_step, donate_argnums=(1,))
        self._chunk = jax.jit(_chunk, donate_argnums=(1,))
        self._verify = jax.jit(_verify, donate_argnums=(1,))
        self._catchup = jax.jit(_catchup, donate_argnums=(1,))
        self._cow = jax.jit(_cow, donate_argnums=(0,))

    # ------------------------------------------------------------- plumbing

    @contextlib.contextmanager
    def _kernel_scope(self):
        """The engine's fixed kernel selections (RHT+qmatmul fusion, paged
        attention kernel-vs-gather), applied to every traced step — each
        jitted function keeps whatever it was traced under."""
        with qops.fusion(self.fused), pops.paged_kernel(self.paged_kernel):
            yield

    def _sample(self, logits: np.ndarray, rid: int, step: int) -> int:
        """One token from ``logits``: greedy argmax at temperature 0, else
        Gumbel-max sampling with a per-(request, step) deterministic RNG."""
        if self.temperature <= 0.0:
            return int(np.argmax(logits))
        rng = np.random.default_rng((self.seed, rid, step))
        g = rng.gumbel(size=logits.shape)
        return int(np.argmax(logits / self.temperature + g))

    def _draft_sample(self, logits: np.ndarray, rid: int, step: int,
                      i: int) -> int:
        """Draft proposal i of a speculative round: greedy argmax, or a
        sample from softmax(logits / T) — the exact distribution the
        acceptance rule divides by."""
        if self.temperature <= 0.0:
            return int(np.argmax(logits))
        rng = np.random.default_rng((self.seed, rid, step, i, 1))
        z = logits.astype(np.float64) / self.temperature
        e = np.exp(z - z.max())
        return int(rng.choice(e.size, p=e / e.sum()))

    # ------------------------------------------------------------ lifecycle

    def start_clock(self, reset: bool = False) -> None:
        """Pin the engine clock's zero (idempotent unless ``reset``).
        ``run`` resets it per call; a continuously-serving front door pins
        it once at boot.  Pass ``reset=True`` after warmup traffic so
        arrival offsets of a timed workload count from now, not from the
        warmup's clock."""
        if reset or self._t0 is None:
            self._t0 = time.monotonic()
            self._last_decode_end = None

    def now(self) -> float:
        """Seconds since the engine clock started (starts it if needed) —
        the time base of ``Request.arrival`` and every result timestamp."""
        self.start_clock()
        return time.monotonic() - self._t0

    def validate(self, req: Request) -> None:
        """Raise ValueError unless the request can ever be served by this
        pool — non-empty prompt, at least one generated token, and a total
        footprint (prompt + max_new, plus speculative lookahead) that fits
        ``max_context`` and the block arena.  The front door calls this at
        the HTTP boundary so a bad request 400s instead of poisoning the
        serving thread."""
        if len(req.prompt) < 1 or req.max_new < 1:
            raise ValueError(
                f"request {req.rid}: needs a non-empty prompt and "
                f"max_new >= 1 (got {len(req.prompt)}, {req.max_new})")
        total = len(req.prompt) + req.max_new
        if total > self.pool.max_context:
            raise ValueError(
                f"request {req.rid}: prompt+max_new = {total} exceeds "
                f"max_context = {self.pool.max_context}")
        need = request_blocks(self.cfg, self.pool, total)
        if need > self.allocator.num_blocks - 1:
            raise ValueError(
                f"request {req.rid}: needs {need} blocks, pool has "
                f"{self.allocator.num_blocks - 1}")

    def submit(self, req: Request) -> None:
        """Queue a request for admission (it will not start before
        ``req.arrival``); validates via :meth:`validate` first."""
        self.validate(req)
        self._pending.append(req)

    def can_admit(self, req: Request) -> bool:
        """Whether admitting ``req`` right now would succeed: a free slot
        and enough allocatable blocks for its full capacity.  Conservative —
        prefix-cache hits can only reduce the fresh-block need (hit blocks
        are either free-listed, LRU-parked, or already referenced, and the
        first two are counted by ``free_blocks``)."""
        if not self.free_slots:
            return False
        need = request_blocks(self.cfg, self.pool,
                              len(req.prompt) + req.max_new)
        return need <= self.allocator.free_blocks

    def _try_admit(self, now: float) -> None:
        # FIFO with head-of-line blocking: admission control is purely
        # "do I have a slot and enough blocks for this request's capacity".
        # (Priority / fair-share ordering lives a layer up, in the
        # front-door Scheduler, which feeds this queue one admissible
        # request at a time.)
        while self._pending and self._pending[0].arrival <= now:
            req = self._pending[0]
            if not self.free_slots:
                return
            # A replayed (preempted) request re-feeds its already-emitted
            # tokens as extra prompt; its total footprint is unchanged
            # (prompt + max_new counts every token exactly once).
            rp = self._replay.get(req.rid)
            served = (np.concatenate([np.asarray(req.prompt, np.int32),
                                      np.asarray(rp.prior, np.int32)])
                      if rp and rp.prior else np.asarray(req.prompt, np.int32))
            total = len(req.prompt) + req.max_new
            need = request_blocks(self.cfg, self.pool, total)
            # Longest cached prefix: whole-block hits are shared (refcount
            # bumped before alloc so allocation pressure can't evict them);
            # a mid-block match is cloned copy-on-write into the request's
            # first private block.  Capped at plen - 1: at least one prompt
            # token is always recomputed to produce first-token logits.
            hits: list[int] = []
            parent, cached, cow_src = None, 0, None
            if self.prefix_cache is not None:
                hits, parent, cached, cow_src = self.prefix_cache.match(
                    served, len(served) - 1)
                for b in hits:
                    self.allocator.incref(b)
                if cow_src is not None:
                    self.allocator.incref(cow_src)
            fresh = self.allocator.alloc(need - len(hits))
            if fresh is None:
                if cow_src is not None:
                    self.allocator.decref(cow_src)
                for b in reversed(hits):      # leaf-first, like _finish
                    self.allocator.decref(b)
                return
            if cow_src is not None:
                # fresh[0] sits at logical index len(hits) — exactly where
                # the partially-matching block's contents belong
                with span(self.stats, "cow"):
                    self.caches = self._cow(self.caches, jnp.int32(cow_src),
                                            jnp.int32(fresh[0]))
                    if self.speculating:
                        # the draft arena shares block tables: clone it too
                        self.draft_caches = self._cow(self.draft_caches,
                                                      jnp.int32(cow_src),
                                                      jnp.int32(fresh[0]))
                self.allocator.decref(cow_src)
                self.stats["prefix_cow"] = self.stats.get("prefix_cow", 0) + 1
            blocks = hits + fresh
            self._pending.popleft()
            self._replay.pop(req.rid, None)
            slot = self.free_slots.pop()
            bt_row = np.zeros(self.table_width, np.int32)
            bt_row[:need] = blocks
            ring_cap = len(blocks) * self.pool.block_size if blocks else 1
            if self.prefix_cache is not None:
                self.stats["prompt_tokens"] = (
                    self.stats.get("prompt_tokens", 0) + len(served))
                self.stats["prefill_tokens_saved"] = (
                    self.stats.get("prefill_tokens_saved", 0) + cached)
                if cached:
                    self.stats["prefix_hits"] = (
                        self.stats.get("prefix_hits", 0) + 1)
            if rp is not None:
                self.stats["replays"] = self.stats.get("replays", 0) + 1
            else:
                self.stats["admitted"] = self.stats.get("admitted", 0) + 1
                self.stats["queue_wait_s"] = (
                    self.stats.get("queue_wait_s", 0.0) + now - req.arrival)
            self._prefilling.append(_InFlight(
                req=req, slot=slot, blocks=blocks, bt_row=bt_row,
                ring_cap=ring_cap, served=served, filled=cached,
                prior=list(rp.prior) if rp else [],
                token_times=list(rp.token_times) if rp else [],
                preemptions=rp.preemptions if rp else 0,
                t_admit=rp.t_admit if rp else now,
                t_first=rp.t_first if rp else 0.0, awaits_chunk=rp is None,
                chain=parent, n_hashed=len(hits), draft_pos=cached))

    def _register_blocks(self, st: _InFlight, seq, upto: int) -> None:
        """Register st's fully-written blocks covering positions < upto
        (KV for those positions is in the arena) into the prefix cache."""
        bs = self.pool.block_size
        while (st.n_hashed + 1) * bs <= upto:
            k = st.n_hashed
            st.chain = self.prefix_cache.register(
                st.chain, seq[k * bs:(k + 1) * bs], int(st.bt_row[k]))
            st.n_hashed += 1

    def _emit(self, st: _InFlight, tok: int, now: float) -> None:
        """One token leaves the engine: record it (and its emission time),
        stamp TTFT on the request's very first token, and fire the
        streaming callback."""
        st.out.append(int(tok))
        st.token_times.append(now)
        if st.t_first == 0.0 and not st.prior:
            st.t_first = now
        if st.req.on_token is not None:
            st.req.on_token(st.req.rid, int(tok), now)

    def _finish(self, st: _InFlight, now: float,
                results: dict[int, RequestResult]) -> None:
        if self.prefix_cache is not None:
            # decode wrote KV through position len(served) + len(out) - 2
            # (the last sampled token was never fed back), so generated
            # tokens extend the cached chain too — multi-turn prompts hit
            # their history
            seq = np.concatenate([st.served,
                                  np.asarray(st.out[:-1], np.int32)])
            self._register_blocks(st, seq, len(seq))
        # children (later blocks) enter the idle LRU first, so eviction
        # under pressure reclaims leaves before the prefixes they chain off
        for b in reversed(st.blocks):
            self.allocator.decref(b)
        self.free_slots.append(st.slot)
        del self._active[st.slot]
        tokens = st.prior + st.out
        results[st.req.rid] = RequestResult(
            rid=st.req.rid, tokens=np.asarray(tokens, np.int32),
            t_admit=st.t_admit, t_first=st.t_first, t_done=now,
            ttft_s=st.t_first - st.req.arrival,
            token_times=np.asarray(st.token_times, np.float64),
            preemptions=st.preemptions, tenant=st.req.tenant)

    def _prefill_one(self, t0: float,
                     results: dict[int, RequestResult]) -> None:
        st = self._prefilling[0]
        plen = len(st.served)
        c = min(self.pool.prefill_chunk, plen - st.filled)
        if self.has_attn:
            c = min(c, st.ring_cap)   # scatter uniqueness within a chunk
        with span(self.stats, "prefill", rid=st.req.rid, tokens=c):
            if st.awaits_chunk:
                st.awaits_chunk = False
                self.stats["prefill_started"] = (
                    self.stats.get("prefill_started", 0) + 1)
                self.stats["prefill_wait_s"] = (
                    self.stats.get("prefill_wait_s", 0.0)
                    + time.monotonic() - t0 - st.t_admit)
            with span(self.stats, "prefill.inputs"):
                args = (jnp.asarray(st.served[st.filled:st.filled + c],
                                    jnp.int32)[None],
                        jnp.int32(st.filled), jnp.int32(st.slot),
                        jnp.asarray(st.bt_row), jnp.int32(st.ring_cap))
            with span(self.stats, "prefill.dispatch"), self._kernel_scope():
                logits, self.caches = self._chunk(self.params, self.caches,
                                                  *args)
                if self.speculating:
                    # the draft arena must hold the prompt too — prefill it
                    # in the same chunks (cheap: the draft's packed codes
                    # are the low-budget quantization); its logits are unused
                    _, self.draft_caches = self._chunk(
                        self.draft_params, self.draft_caches, *args)
            st.filled += c
            if self.speculating:
                st.draft_pos = st.filled
            self.stats["prefill_chunks"] = (
                self.stats.get("prefill_chunks", 0) + 1)
            self.stats["prefill_tokens"] = (
                self.stats.get("prefill_tokens", 0) + c)
            if self.prefix_cache is not None:
                # blocks completed by this chunk are fully written: publish
                # them so concurrent requests sharing the prompt hit them
                # immediately
                self._register_blocks(st, st.served, st.filled)
            if st.filled < plen:
                return
            self._prefilling.popleft()
            with span(self.stats, "prefill.pull"):
                row = np.asarray(logits[0])
            with span(self.stats, "prefill.sample"):
                tok = self._sample(row, st.req.rid, st.n_done)
                now = time.monotonic() - t0   # after the step has completed
                self._emit(st, tok, now)
                self._active[st.slot] = st   # _finish expects it registered
                if st.n_done >= st.req.max_new or tok == st.req.eos:
                    self._finish(st, now, results)

    def _decode_once(self, t0: float,
                     results: dict[int, RequestResult]) -> None:
        s = self.pool.max_slots
        with span(self.stats, "decode.inputs"):
            tokens = np.zeros((s, 1), np.int32)
            pos = np.zeros(s, np.int32)
            active = np.zeros(s, bool)
            bts = np.zeros((s, self.table_width), np.int32)
            ring = np.ones(s, np.int32)
            for slot, st in self._active.items():
                tokens[slot, 0] = st.out[-1]
                pos[slot] = len(st.served) + len(st.out) - 1
                active[slot] = True
                bts[slot] = st.bt_row
                ring[slot] = st.ring_cap
            args = tuple(map(jnp.asarray, (tokens, pos, active, bts, ring)))
        with span(self.stats, "decode.dispatch"), self._kernel_scope():
            logits, self.caches = self._step(self.params, self.caches, *args)
        with span(self.stats, "decode.pull"):
            logits = np.asarray(logits)
        with span(self.stats, "decode.sample"):
            now = time.monotonic() - t0       # after the step has completed
            self._count_decode_step("decode_steps")
            for slot in list(self._active):
                st = self._active[slot]
                tok = self._sample(logits[slot], st.req.rid, st.n_done)
                self._emit(st, tok, now)
                if st.n_done >= st.req.max_new or tok == st.req.eos:
                    self._finish(st, now, results)

    def _count_decode_step(self, key: str) -> None:
        """Count one decode step (``key``) with the slots it served
        (``occupancy``) and those held by requests still prefilling."""
        self.stats[key] = self.stats.get(key, 0) + 1
        self.stats.setdefault("occupancy", []).append(
            len(self._active) / self.pool.max_slots)
        self.stats["prefill_slot_steps"] = (
            self.stats.get("prefill_slot_steps", 0) + len(self._prefilling))

    # ---------------------------------------------------------- speculation

    def _spec_decode_once(self, t0: float,
                          results: dict[int, RequestResult]) -> None:
        """One draft-propose / target-verify round over the whole slot set.

        Draft phase: a fixed-shape 2-token catch-up step (feeds the tokens
        at positions pos-1 and pos; the first position's arena write is
        masked unless that slot has a post-all-accept hole) followed by k-1
        single-token draft steps, yielding k proposals per slot and the
        draft logits each was sampled from.  Verify phase: the target
        scores [last, d_1..d_k] at positions pos..pos+k in one batched
        ``decode_verify_paged`` dispatch.  Acceptance runs host-side per
        slot (``speculative_accept``), emitting 1..k+1 tokens per round.
        """
        s, k = self.pool.max_slots, self.speculate
        with span(self.stats, "spec.inputs"):
            catch = np.zeros((s, 2), np.int32)    # tokens at pos-1, pos
            pos = np.zeros(s, np.int32)
            active = np.zeros(s, bool)
            hole = np.zeros(s, bool)
            bts = np.zeros((s, self.table_width), np.int32)
            ring = np.ones(s, np.int32)
            for slot, st in self._active.items():
                p = len(st.served) + len(st.out) - 1
                pos[slot] = p
                catch[slot, 0] = (st.out[-2] if len(st.out) >= 2
                                  else st.served[-1])
                catch[slot, 1] = st.out[-1]
                active[slot] = True
                # after an all-accept round the bonus token's predecessor
                # (d_k) was never fed to the draft: position p-1 is a hole
                # the catch-up step must commit; otherwise the rewrite is
                # masked so shared prefix-cache blocks are never touched
                hole[slot] = st.draft_pos == p - 1
                bts[slot] = st.bt_row
                ring[slot] = st.ring_cap
            wmask = np.ones((s, 2), bool)
            wmask[:, 0] = hole
            tables = tuple(map(jnp.asarray, (active, bts, ring)))
            catch_args = (jnp.asarray(catch), jnp.asarray(pos - 1), *tables,
                          jnp.asarray(wmask))
        with span(self.stats, "spec.dispatch"), self._kernel_scope():
            dlog, self.draft_caches = self._catchup(
                self.draft_params, self.draft_caches, *catch_args)
        with span(self.stats, "spec.pull"):
            dl = np.asarray(dlog[:, 1])       # draft logits at position pos
        draft_logits = np.zeros((s, k) + dl.shape[1:], np.float32)
        draft_tokens = np.zeros((s, k), np.int32)
        toks = np.zeros((s, 1), np.int32)
        for i in range(k):
            with span(self.stats, "spec.sample"):
                draft_logits[:, i] = dl
                for slot, st in self._active.items():
                    d = self._draft_sample(dl[slot], st.req.rid, st.n_done,
                                           i)
                    draft_tokens[slot, i] = d
                    toks[slot, 0] = d
            if i < k - 1:
                with span(self.stats, "spec.dispatch"), self._kernel_scope():
                    nxt, self.draft_caches = self._draft_step(
                        self.draft_params, self.draft_caches,
                        jnp.asarray(toks), jnp.asarray(pos + 1 + i), *tables)
                with span(self.stats, "spec.pull"):
                    dl = np.asarray(nxt)
        with span(self.stats, "spec.dispatch"), self._kernel_scope():
            verify_toks = np.concatenate([catch[:, 1:2], draft_tokens],
                                         axis=1)
            tlog, self.caches = self._verify(
                self.params, self.caches, jnp.asarray(verify_toks),
                jnp.asarray(pos), *tables, jnp.ones((s, k + 1), bool))
        with span(self.stats, "spec.pull"):
            tlog = np.asarray(tlog)
        with span(self.stats, "spec.sample"):
            now = time.monotonic() - t0       # after the step has completed
            self._count_decode_step("spec_rounds")
            for slot in list(self._active):
                st = self._active[slot]
                # greedy needs no RNG (and warmup requests may carry
                # negative rids, which SeedSequence rejects)
                rng = (np.random.default_rng(
                           (self.seed, st.req.rid, st.n_done, 7))
                       if self.temperature > 0.0 else None)
                emitted, n_acc = speculative_accept(
                    tlog[slot], draft_logits[slot], draft_tokens[slot],
                    self.temperature, rng)
                self.stats["spec_proposed"] = (
                    self.stats.get("spec_proposed", 0) + k)
                self.stats["spec_accepted"] = (
                    self.stats.get("spec_accepted", 0) + n_acc)
                p = int(pos[slot])
                # draft KV is valid through the last accepted draft position
                # (the replacement/bonus token is never fed to the draft)
                st.draft_pos = min(p + n_acc + 1, p + k)
                for tok in emitted:
                    self._emit(st, tok, now)
                    if (st.n_done >= st.req.max_new or tok == st.req.eos):
                        break
                if st.n_done >= st.req.max_new or st.out[-1] == st.req.eos:
                    self._finish(st, now, results)

    # --------------------------------------------------------- preemption

    def _evict_inflight(self, rid: int) -> Optional[_InFlight]:
        """Pull request ``rid`` out of the prefill/decode sets: register
        its fully-written blocks in the prefix cache (so they park on the
        allocator's LRU with their KV intact rather than being recomputed
        from scratch later), release its block refs, and free its slot.
        Returns the removed state, or None if ``rid`` is not in flight."""
        st = next((s for s in self._active.values() if s.req.rid == rid),
                  None)
        from_active = st is not None
        if st is None:
            st = next((s for s in self._prefilling if s.req.rid == rid),
                      None)
        if st is None:
            return None
        if self.prefix_cache is not None:
            # KV is written through len(served)+len(out)-2 when decoding
            # (the newest sampled token was never fed back); mid-prefill,
            # _prefill_one already registered every completed block.
            if st.out:
                seq = np.concatenate([st.served,
                                      np.asarray(st.out[:-1], np.int32)])
                self._register_blocks(st, seq, len(seq))
        for b in reversed(st.blocks):
            self.allocator.decref(b)
        self.free_slots.append(st.slot)
        if from_active:
            del self._active[st.slot]
        else:
            self._prefilling.remove(st)
        return st

    def preempt(self, rid: int) -> Request | None:
        """Drop-and-replay preemption (DESIGN.md §12): evict request
        ``rid``'s KV blocks and return its ``Request`` so a scheduler can
        requeue it; ``None`` if ``rid`` is not in flight.

        The victim's generated KV blocks are registered in the prefix
        cache before its refs are released, so on a cacheable engine the
        replay's prefill is a warm walk over its own cached history and
        recompute is one chunk, not the whole sequence (unless allocation
        pressure reclaimed the blocks in between).  The replay
        continuation (already-emitted tokens, first-incarnation
        timestamps) is held internally by rid and picked up when the same
        rid is resubmitted; emitted tokens are re-fed as extra prompt, so
        a preempted-then-replayed greedy request is token-identical to an
        uninterrupted run (pinned in tests/test_frontdoor.py)."""
        st = self._evict_inflight(rid)
        if st is None:
            return None
        self._replay[rid] = _Replay(
            prior=st.prior + st.out, t_admit=st.t_admit, t_first=st.t_first,
            token_times=list(st.token_times),
            preemptions=st.preemptions + 1)
        self.stats["preemptions"] = self.stats.get("preemptions", 0) + 1
        return st.req

    def cancel(self, rid: int) -> bool:
        """Abort request ``rid`` wherever it is — queued, prefilling, or
        decoding — freeing its resources and dropping any replay
        continuation (the front door calls this when a streaming client
        disconnects).  Returns True if anything was removed."""
        for i, r in enumerate(self._pending):
            if r.rid == rid:
                del self._pending[i]
                self._replay.pop(rid, None)
                self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
                return True
        had_replay = self._replay.pop(rid, None) is not None
        st = self._evict_inflight(rid)
        if st is not None or had_replay:
            self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
        return st is not None or had_replay

    def inflight(self) -> list:
        """Scheduler's view of every request currently holding (or queued
        for) resources: ``(request, phase, tokens_done, t_admit)`` tuples
        with phase in ``{"pending", "prefill", "decode"}``.  ``prefill`` and
        ``decode`` entries hold a slot and blocks and are preemptible."""
        out = [(r, "pending", 0, r.arrival) for r in self._pending]
        out += [(s.req, "prefill", s.n_done, s.t_admit)
                for s in self._prefilling]
        out += [(s.req, "decode", s.n_done, s.t_admit)
                for s in self._active.values()]
        return out

    # ------------------------------------------------------------------ run

    def poll(self) -> bool:
        """Whether the engine has outstanding work (queued, prefilling, or
        decoding requests).  Preempted-but-not-yet-resubmitted requests are
        the *scheduler's* outstanding work, not the engine's."""
        return bool(self._pending or self._prefilling or self._active)

    @property
    def active_count(self) -> int:
        """Requests currently decoding (the population an SLO protects)."""
        return len(self._active)

    def step(self, *, prefill: bool = True
             ) -> dict[int, RequestResult]:
        """ONE re-entrant scheduler iteration: admit due requests, run one
        prompt chunk (unless ``prefill=False`` — the SLO controller's
        chunked-prefill throttle), then one decode step over the slot set.
        Returns the requests that finished during this call (streaming
        consumers also saw their tokens via ``on_token``).  ``run`` is a
        drain loop over this; a front door calls it forever."""
        results: dict[int, RequestResult] = {}
        self.start_clock()
        with span(self.stats, "step"):
            with span(self.stats, "admit"):
                self._try_admit(self.now())
            if prefill and self._prefilling:
                self._prefill_one(self._t0, results)
            if self._active:
                if self.speculate:
                    with span(self.stats, "spec", slots=len(self._active)):
                        self._spec_decode_once(self._t0, results)
                else:
                    with span(self.stats, "decode",
                              slots=len(self._active)):
                        self._decode_once(self._t0, results)
                end = time.monotonic()
                if self._last_decode_end is not None:
                    self.decode_gaps.append(end - self._last_decode_end)
                self._last_decode_end = end
            else:
                # no decode ran: the next gap would measure idleness, not
                # scheduling interference — restart the gap baseline
                self._last_decode_end = None
        return results

    def finalize_stats(self) -> dict:
        """Fold the per-step counters into the summary numbers (mean
        occupancy, acceptance rate, prefix hit rate); returns ``stats``."""
        occ = self.stats.get("occupancy", [])
        self.stats["mean_occupancy"] = float(np.mean(occ)) if occ else 0.0
        if self.speculate:
            prop = self.stats.get("spec_proposed", 0)
            self.stats["acceptance_rate"] = (
                self.stats.get("spec_accepted", 0) / prop if prop else 0.0)
        if self.prefix_cache is not None:
            pt = self.stats.get("prompt_tokens", 0)
            self.stats["prefix_hit_rate"] = (
                self.stats.get("prefill_tokens_saved", 0) / pt if pt else 0.0)
            self.stats["prefix_evictions"] = self.prefix_cache.evictions
            self.stats["prefix_cached_blocks"] = len(self.prefix_cache)
        return self.stats

    def run(self, requests: list[Request] | None = None
            ) -> dict[int, RequestResult]:
        """Serve until every submitted request completes.  Returns
        rid -> RequestResult; aggregate stats land in ``self.stats``
        (occupancy, prefill/prefix counters, and — when speculating —
        spec_rounds / spec_proposed / spec_accepted / acceptance_rate)."""
        for r in requests or []:
            self.submit(r)
        self._pending = collections.deque(
            sorted(self._pending, key=lambda r: r.arrival))
        results: dict[int, RequestResult] = {}
        self._t0 = time.monotonic()       # each run() restarts the clock
        self._last_decode_end = None
        while self.poll():
            results.update(self.step())
            if not self._active and not self._prefilling and self._pending:
                wait = self._pending[0].arrival - self.now()
                if wait > 0:
                    time.sleep(min(wait, 0.05))
        self.finalize_stats()
        return results
