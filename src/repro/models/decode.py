"""Serving paths: cache init, prefill (sequence -> logits + caches), and the
single-token decode step, for every mixer family.

Cache layout mirrors ``params["layers"]``: one stacked cache tree per scan
position, so decode scans layers and caches together.  Sliding-window archs
get ring-buffered KV caches (capacity = window); attention-free mixers carry
O(1) recurrent state — which is precisely why they are the archs that can
serve the long_500k cell (DESIGN.md §4).

Quantized decode is memory-bound: every linear here dispatches (via
``common.linear`` / ``moe._expert_matmul``) to the fused RHT+qmatmul kernel
(DESIGN.md §6), so single-token weights move HBM->VMEM packed at b/16 of the
bf16 cost and the rotation happens in VMEM — no rotated-activation round trip
between kernels.

The ``*_paged`` variants at the bottom are the continuous-batching serving
path (DESIGN.md §7): attention K/V lives in a shared block arena addressed
via per-request block tables, recurrent/MLA state in per-slot arrays, and
the decode step takes fixed-shape (tokens, pos, active, block_tables,
ring_cap) arrays so it compiles once no matter how the batch churns.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.runtime import tp as tpmod

from . import attention as attnmod
from . import mla as mlamod
from . import rglru as rglrumod
from . import rwkv6 as rwkvmod
from .attention import KVCache
from .common import (apply_mrope, apply_norm, apply_rope, linear,
                     rms_norm, sinusoidal_positions)
from .config import ModelConfig
from .transformer import (_ffn_apply, _qk_normalize, embed_tokens, encode,
                          get_layer, layer_seq, layers_scannable)

# ----------------------------------------------------------- cache structs


def attn_capacity(cfg: ModelConfig, context: int) -> int:
    return min(context, cfg.window) if cfg.window else context


def init_layer_cache(cfg: ModelConfig, mixer: str, b: int, context: int,
                     dtype=jnp.float32, encoder_out=None, lp=None) -> dict:
    cache: dict[str, Any] = {}
    if mixer == "attn":
        cache["kv"] = KVCache.init(b, attn_capacity(cfg, context), cfg.n_kv,
                                   cfg.hd, dtype)
    elif mixer == "mla":
        cache["mla"] = mlamod.MLACache.init(b, context, cfg.mla.kv_lora,
                                            cfg.mla.qk_rope, dtype)
    elif mixer == "rwkv":
        cache["rwkv"] = rwkvmod.RWKVState.init(b, cfg.n_heads, cfg.hd,
                                               cfg.d_model, dtype)
    elif mixer == "rglru":
        cache["rglru"] = rglrumod.RGLRUState.init(
            b, cfg.rglru_width or cfg.d_model, dtype)
    if cfg.enc_dec:
        assert encoder_out is not None and lp is not None
        t = encoder_out.shape[1]
        k = linear(lp["xattn"]["wk"], encoder_out).reshape(
            b, t, cfg.n_kv, cfg.hd)
        v = linear(lp["xattn"]["wv"], encoder_out).reshape(
            b, t, cfg.n_kv, cfg.hd)
        cache["xk"], cache["xv"] = k, v
    return cache


def init_caches(cfg: ModelConfig, params: dict, b: int, context: int,
                dtype=jnp.float32, encoder_out=None) -> list:
    """One stacked cache tree per scan position (parallel to params layers)."""
    pat, p = cfg.pattern, cfg.scan_period
    caches = []
    for j in range(p):
        stack = params["layers"][j]
        n_j = (len(stack) if isinstance(stack, list)
               else jax.tree.leaves(stack)[0].shape[0])

        def one(i):
            lp = (stack[i] if isinstance(stack, list)
                  else jax.tree.map(lambda a: a[i], stack))
            return init_layer_cache(cfg, pat[j], b, context, dtype,
                                    encoder_out, lp)
        caches.append(jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                   *[one(i) for i in range(n_j)]))
    return caches


# ----------------------------------------------------------------- prefill


def _attn_qkv(cfg: ModelConfig, p: dict, hn: jax.Array, positions):
    """Shared attention-mixer projection: q/k/v + qk-norm + rope/mrope.

    hn (B, S, d); positions (B, S), or (3, B, S) for mrope.  Used by every
    serving path (prefill, decode, and their paged variants) so positional
    handling can't drift between them.  Head counts are derived from the
    projection outputs, not ``cfg``: under tensor-parallel serving
    (runtime/tp.py) ``wq``/``wk``/``wv`` arrive column-sharded inside
    ``shard_map`` and each shard sees its local head slice; rope/qk-norm
    are per-head so they apply to the slice unchanged.
    """
    b, s, _ = hn.shape
    hd = cfg.hd
    q = linear(p["wq"], hn).reshape(b, s, -1, hd)
    k = linear(p["wk"], hn).reshape(b, s, -1, hd)
    v = linear(p["wv"], hn).reshape(b, s, -1, hd)
    q, k = _qk_normalize(p, q, k)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos == "mrope":
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    return q, k, v


def _ring_fill(cache: KVCache, k: jax.Array, v: jax.Array) -> KVCache:
    """Fill a ring cache from a full prefix (B, S, kv, hd): keep last cap."""
    cap = cache.k.shape[1]
    s = k.shape[1]
    if s <= cap:
        return KVCache(k=cache.k.at[:, :s].set(k.astype(cache.k.dtype)),
                       v=cache.v.at[:, :s].set(v.astype(cache.v.dtype)))
    tail_t = jnp.arange(s - cap, s)
    slots = tail_t % cap
    return KVCache(k=cache.k.at[:, slots].set(k[:, tail_t].astype(cache.k.dtype)),
                   v=cache.v.at[:, slots].set(v[:, tail_t].astype(cache.v.dtype)))


def layer_prefill(cfg: ModelConfig, mixer: str, lp: dict, h: jax.Array,
                  positions, cache: dict, encoder_out=None):
    """Sequence forward through one layer, also filling its cache.
    Returns (h, aux, new_cache)."""
    b, s, d = h.shape
    hn = apply_norm(cfg.norm, h, lp["ln1"])
    new_cache = dict(cache)
    if mixer == "attn":
        p = lp["attn"]
        q, k, v = _attn_qkv(cfg, p, hn, positions)
        out = attnmod.flash_attention(q, k, v, causal=True, window=cfg.window,
                                      expand_kv=cfg.expand_kv)
        mix = linear(p["wo"], out.reshape(b, s, cfg.n_heads * cfg.hd))
        from repro.runtime.actsharding import shard_named
        new_cache["kv"] = _ring_fill(cache["kv"], shard_named(k, "kv"),
                                     shard_named(v, "kv"))
    elif mixer == "mla":
        m, p = cfg.mla, lp["mla"]
        mix = mlamod.mla_full(p, hn, m, positions)
        c_kv, k_rope = mlamod._project_kv_latent(p, hn, m, positions, None, "")
        new_cache["mla"] = mlamod.MLACache(
            c_kv=cache["mla"].c_kv.at[:, :s].set(
                c_kv.astype(cache["mla"].c_kv.dtype)),
            k_rope=cache["mla"].k_rope.at[:, :s].set(
                k_rope.astype(cache["mla"].k_rope.dtype)))
    elif mixer == "rwkv":
        mix, st = rwkvmod.time_mix(lp["tm"], hn, n_heads=cfg.n_heads,
                                   head_dim=cfg.hd, return_state=True)
        new_cache["rwkv"] = rwkvmod.RWKVState(
            s=st, x_prev_tm=hn[:, -1].astype(cache["rwkv"].x_prev_tm.dtype),
            x_prev_cm=cache["rwkv"].x_prev_cm)
    elif mixer == "rglru":
        mix, st = rglrumod.rglru_block(lp["rglru"], hn, return_state=True)
        new_cache["rglru"] = st
    else:
        raise ValueError(mixer)
    h = h + mix.astype(h.dtype)
    if cfg.enc_dec:
        hx = apply_norm(cfg.norm, h, lp["ln_x"])
        q = linear(lp["xattn"]["wq"], hx).reshape(b, s, cfg.n_heads, cfg.hd)
        out = attnmod.flash_attention(
            q, cache["xk"], cache["xv"], causal=False)
        h = h + linear(lp["xattn"]["wo"], out.reshape(b, s, -1))
    h2 = apply_norm(cfg.norm, h, lp["ln2"])
    if mixer == "rwkv":
        y = rwkvmod.channel_mix(lp["cm"], h2)
        new_cache["rwkv"] = rwkvmod.RWKVState(
            s=new_cache["rwkv"].s, x_prev_tm=new_cache["rwkv"].x_prev_tm,
            x_prev_cm=h2[:, -1].astype(cache["rwkv"].x_prev_cm.dtype))
        aux = 0.0
    else:
        y, aux = _ffn_apply(cfg, lp, h2, None, "pf")
    from repro.runtime.actsharding import shard_hidden
    return shard_hidden(h + y.astype(h.dtype)), aux, new_cache


def _apply_layers(cfg: ModelConfig, params: dict, caches: list, h: jax.Array,
                  layer_fn, scan: bool):
    """Shared layer-stack driver for every serving path.

    ``layer_fn(mixer, lp, h, cache) -> (h, new_cache)`` is applied to the
    layers in execution order, scanning full periods of the mixer pattern
    when the param/cache trees are stackable and unrolling otherwise
    (quantized models with heterogeneous per-layer bit widths).  Returns
    (h, new_caches) with new_caches stacked parallel to ``params['layers']``.
    """
    scan = scan and layers_scannable(params)
    pat, p_period = cfg.pattern, cfg.scan_period
    n_full = cfg.n_layers // p_period
    rem = cfg.n_layers % p_period
    new_caches = [None] * p_period

    if scan and n_full > 0:
        full_stacks = [jax.tree.map(lambda a: a[:n_full], st)
                       for st in params["layers"]]
        full_caches = [jax.tree.map(lambda a: a[:n_full], cs) for cs in caches]

        def body(hh, xs):
            lps, cs = xs
            outs = []
            for j in range(p_period):
                hh, nc = layer_fn(pat[j], lps[j], hh, cs[j])
                outs.append(nc)
            return hh, tuple(outs)

        h, scanned = jax.lax.scan(body, h, (tuple(full_stacks),
                                            tuple(full_caches)))
        new_caches = list(scanned)
        for j in range(rem):
            lp = jax.tree.map(lambda a: a[n_full], params["layers"][j])
            cs = jax.tree.map(lambda a: a[n_full], caches[j])
            h, nc = layer_fn(pat[j], lp, h, cs)
            new_caches[j] = jax.tree.map(
                lambda full, one: jnp.concatenate([full, one[None]], 0),
                new_caches[j], nc)
    else:
        percall = [[] for _ in range(p_period)]
        for i in range(cfg.n_layers):
            jpos, idx = i % p_period, i // p_period
            lp = get_layer(params, jpos, idx)
            cs = jax.tree.map(lambda a: a[idx], caches[jpos])
            h, nc = layer_fn(pat[i], lp, h, cs)
            percall[jpos].append(nc)
        new_caches = [jax.tree.map(lambda *xs: jnp.stack(xs, 0), *cl)
                      for cl in percall]
    return h, new_caches


def prefill(cfg: ModelConfig, params: dict, tokens=None, *, embeds=None,
            positions=None, context: int | None = None, enc_embeds=None,
            cache_dtype=jnp.float32, scan: bool = True):
    """Run the prefix, return (logits (B, S, V), caches, pos = S)."""
    h = embeds if embeds is not None else embed_tokens(cfg, params, tokens)
    b, s, d = h.shape
    context = context or s
    encoder_out = None
    if cfg.enc_dec:
        encoder_out = encode(cfg, params, enc_embeds, scan=scan)
    if cfg.pos == "sinusoidal":
        h = h + sinusoidal_positions(s, d).astype(h.dtype)[None]
    if positions is None:
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        positions = (jnp.broadcast_to(pos[None], (3, b, s))
                     if cfg.pos == "mrope" else pos)
    caches = init_caches(cfg, params, b, context, cache_dtype, encoder_out)

    def fn(mixer, lp, hh, cs):
        hh, _, nc = layer_prefill(cfg, mixer, lp, hh, positions, cs,
                                  encoder_out)
        return hh, nc

    h, new_caches = _apply_layers(cfg, params, caches, h, fn, scan)
    h = apply_norm(cfg.norm, h, params["final_norm"])
    logits = linear(params["lm_head"], h)
    return logits, new_caches, jnp.int32(s)


# ------------------------------------------------------------ decode step


def layer_decode(cfg: ModelConfig, mixer: str, lp: dict, h: jax.Array,
                 cache: dict, pos: jax.Array):
    """One layer, one token: h (B, 1, d) -> (h, new_cache)."""
    b = h.shape[0]
    hn = apply_norm(cfg.norm, h, lp["ln1"])
    new_cache = dict(cache)
    if mixer == "attn":
        p = lp["attn"]
        posb = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
        if cfg.pos == "mrope":
            posb = jnp.broadcast_to(pos, (3, b, 1)).astype(jnp.int32)
        q, k, v = _attn_qkv(cfg, p, hn, posb)
        kvc = attnmod.cache_insert(cache["kv"], k, v, pos)
        out = attnmod.decode_attention(q, kvc, pos + 1)
        mix = linear(p["wo"], out.reshape(b, 1, cfg.n_heads * cfg.hd))
        new_cache["kv"] = kvc
    elif mixer == "mla":
        mix, mc = mlamod.mla_decode(lp["mla"], hn, cfg.mla, cache["mla"], pos)
        new_cache["mla"] = mc
    elif mixer == "rwkv":
        mix, st = rwkvmod.time_mix_decode(lp["tm"], hn[:, 0],
                                          cache["rwkv"], n_heads=cfg.n_heads,
                                          head_dim=cfg.hd)
        mix = mix[:, None, :]
        new_cache["rwkv"] = st
    elif mixer == "rglru":
        mix, st = rglrumod.rglru_decode(lp["rglru"], hn[:, 0], cache["rglru"])
        mix = mix[:, None, :]
        new_cache["rglru"] = st
    else:
        raise ValueError(mixer)
    h = h + mix.astype(h.dtype)
    if cfg.enc_dec:
        hx = apply_norm(cfg.norm, h, lp["ln_x"])
        q = linear(lp["xattn"]["wq"], hx).reshape(b, 1, cfg.n_heads, cfg.hd)
        t = cache["xk"].shape[1]
        out = attnmod.decode_attention(
            q, KVCache(k=cache["xk"], v=cache["xv"]), jnp.int32(t))
        h = h + linear(lp["xattn"]["wo"], out.reshape(b, 1, -1))
    h2 = apply_norm(cfg.norm, h, lp["ln2"])
    if mixer == "rwkv":
        y = rwkvmod.channel_mix(lp["cm"], h2[:, 0],
                                new_cache["rwkv"].x_prev_cm)[:, None, :]
        st = new_cache["rwkv"]
        new_cache["rwkv"] = rwkvmod.RWKVState(
            s=st.s, x_prev_tm=st.x_prev_tm,
            x_prev_cm=h2[:, 0].astype(st.x_prev_cm.dtype))
    else:
        y, _ = _ffn_apply(cfg, lp, h2, None, "dec")
    return h + y.astype(h.dtype), new_cache


def decode_step(cfg: ModelConfig, params: dict, caches: list,
                tokens: jax.Array, pos: jax.Array, scan: bool = True):
    """One token for the whole model: tokens (B, 1) -> (logits (B, V),
    new caches).  ``pos`` = number of tokens already in the cache."""
    h = embed_tokens(cfg, params, tokens)
    if cfg.pos == "sinusoidal":
        d = h.shape[-1]
        table = sinusoidal_positions(caches_context(caches, cfg), d)
        h = h + jax.lax.dynamic_slice_in_dim(table, pos, 1, 0)[None].astype(h.dtype)

    def fn(mixer, lp, hh, cs):
        return layer_decode(cfg, mixer, lp, hh, cs, pos)

    h, new_caches = _apply_layers(cfg, params, caches, h, fn, scan)
    h = apply_norm(cfg.norm, h, params["final_norm"])
    logits = linear(params["lm_head"], h)
    return logits[:, 0], new_caches


# ------------------------------------------------- paged serving variants
#
# The continuous-batching engine (repro/serve) keeps one fixed set of ``S``
# slots; attention K/V lives in a shared block arena addressed through
# per-slot block tables, and MLA/RWKV/RG-LRU recurrent state lives in
# per-slot arrays.  Every argument that changes as the batch composition
# churns (tokens, positions, active mask, block tables, ring capacities) is
# an *array* of static shape, so the jitted step traces exactly once.


def _mask_state(old, new, active: jax.Array):
    """Keep ``old`` state rows where ``active`` is False (slot-array pytrees)."""
    def sel(o, n):
        m = active.reshape(active.shape[0], *([1] * (o.ndim - 1)))
        return jnp.where(m, n.astype(o.dtype), o)
    return jax.tree.map(sel, old, new)


def layer_decode_paged(cfg: ModelConfig, mixer: str, lp: dict, h: jax.Array,
                       cache: dict, pos: jax.Array, active: jax.Array,
                       block_tables: jax.Array, ring_cap: jax.Array):
    """One layer, one token per slot, against the paged cache pool.

    h (S, 1, d); pos (S,) per-slot token counts (the fed token's absolute
    position); active (S,) request-occupancy mask; block_tables (S, MB);
    ring_cap (S,) per-slot ring capacities in tokens.
    """
    b = h.shape[0]
    hn = apply_norm(cfg.norm, h, lp["ln1"])
    new_cache = dict(cache)
    if mixer == "attn":
        p = lp["attn"]
        posb = pos[:, None].astype(jnp.int32)
        if cfg.pos == "mrope":
            posb = jnp.broadcast_to(pos[None, :, None], (3, b, 1)).astype(jnp.int32)
        q, k, v = _attn_qkv(cfg, p, hn, posb)
        block_size = cache["k"].shape[2]
        pb, off = attnmod.paged_write_indices(pos, ring_cap, block_tables,
                                              block_size, active)
        k_arena = attnmod.paged_write(cache["k"], pb, off, k[:, 0])
        v_arena = attnmod.paged_write(cache["v"], pb, off, v[:, 0])
        out = attnmod.paged_decode_attention(q, k_arena, v_arena, block_tables,
                                             pos + 1, ring_cap,
                                             window=cfg.window)
        out = tpmod.gather_heads(out, cfg.n_heads)
        mix = linear(p["wo"], out.reshape(b, 1, -1))
        new_cache["k"], new_cache["v"] = k_arena, v_arena
    elif mixer == "mla":
        mix, mc = mlamod.mla_decode_paged(lp["mla"], hn, cfg.mla,
                                          cache["mla"], pos, active)
        new_cache["mla"] = mc
    elif mixer == "rwkv":
        mix, st = rwkvmod.time_mix_decode(lp["tm"], hn[:, 0], cache["rwkv"],
                                          n_heads=cfg.n_heads,
                                          head_dim=cfg.hd)
        mix = mix[:, None, :]
        new_cache["rwkv"] = _mask_state(cache["rwkv"], st, active)
    elif mixer == "rglru":
        mix, st = rglrumod.rglru_decode(lp["rglru"], hn[:, 0], cache["rglru"])
        mix = mix[:, None, :]
        new_cache["rglru"] = _mask_state(cache["rglru"], st, active)
    else:
        raise ValueError(mixer)
    h = h + mix.astype(h.dtype)
    h2 = apply_norm(cfg.norm, h, lp["ln2"])
    if mixer == "rwkv":
        y = rwkvmod.channel_mix(lp["cm"], h2[:, 0],
                                new_cache["rwkv"].x_prev_cm)[:, None, :]
        st = new_cache["rwkv"]
        new_cache["rwkv"] = _mask_state(
            st, rwkvmod.RWKVState(s=st.s, x_prev_tm=st.x_prev_tm,
                                  x_prev_cm=h2[:, 0]), active)
    else:
        y, _ = _ffn_apply(cfg, lp, h2, None, "dec")
    return h + y.astype(h.dtype), new_cache


def decode_step_paged(cfg: ModelConfig, params: dict, caches: list,
                      tokens: jax.Array, pos: jax.Array, active: jax.Array,
                      block_tables: jax.Array, ring_cap: jax.Array,
                      scan: bool = True):
    """One decode step for the whole slot set: tokens (S, 1) -> (logits
    (S, V), new caches).  Inactive slots run inert (embeddings zeroed, cache
    writes redirected/no-op'd) so the compiled step is reused unchanged while
    requests come and go.
    """
    if cfg.enc_dec:
        raise NotImplementedError(
            "paged serving does not support encoder-decoder archs")
    h = embed_tokens(cfg, params, tokens)
    if cfg.pos == "sinusoidal":
        d = h.shape[-1]
        table = sinusoidal_positions(caches_context(caches, cfg), d)
        h = h + table[jnp.minimum(pos, table.shape[0] - 1)][:, None].astype(h.dtype)
    h = jnp.where(active[:, None, None], h, 0)

    def fn(mixer, lp, hh, cs):
        return layer_decode_paged(cfg, mixer, lp, hh, cs, pos, active,
                                  block_tables, ring_cap)

    h, new_caches = _apply_layers(cfg, params, caches, h, fn, scan)
    h = apply_norm(cfg.norm, h, params["final_norm"])
    logits = tpmod.gather_cols(linear(params["lm_head"], h), cfg.vocab)
    return logits[:, 0], new_caches


def layer_verify_paged(cfg: ModelConfig, mixer: str, lp: dict, h: jax.Array,
                       cache: dict, pos0: jax.Array, active: jax.Array,
                       block_tables: jax.Array, ring_cap: jax.Array,
                       write_mask: jax.Array):
    """One layer, W tokens per slot, against the paged pool (speculative
    verify / draft catch-up).

    h (S, W, d); pos0 (S,) each slot's first absolute position; active (S,);
    block_tables (S, MB); ring_cap (S,); write_mask (S, W) selects which of
    the W positions commit KV to the arena (masked writes land on the null
    block).  Attention gathers pre-``pos0`` history from the arena exactly
    like chunked prefill and is causal within the W-token span, so the
    logits at position ``pos0 + i`` condition on the first i fed tokens —
    the property the acceptance rule needs.  Attention-only: recurrent/MLA
    state is sequential (re-feeding positions would corrupt it), which is
    why those archs bypass speculation (DESIGN.md §9).
    """
    if mixer != "attn":
        raise NotImplementedError(
            f"speculative verify supports attention mixers only (got "
            f"{mixer!r}); recurrent/MLA archs bypass speculation")
    from repro.kernels.paged_attention import ops as pops  # late: no cycle
    b, w, d = h.shape
    hn = apply_norm(cfg.norm, h, lp["ln1"])
    new_cache = dict(cache)
    positions = pos0[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    p = lp["attn"]
    posb = positions
    if cfg.pos == "mrope":
        posb = jnp.broadcast_to(positions[None], (3, b, w)).astype(jnp.int32)
    q, k, v = _attn_qkv(cfg, p, hn, posb)
    block_size = cache["k"].shape[2]
    pb, off = attnmod.paged_multi_write_indices(positions, ring_cap,
                                                block_tables, block_size,
                                                write_mask)
    new_cache["k"] = attnmod.paged_write(cache["k"], pb, off, k)
    new_cache["v"] = attnmod.paged_write(cache["v"], pb, off, v)
    if pops.kernel_enabled():
        # kernel path: the span's K/V is committed above, so one flash-decode
        # sweep over the arena covers history + span (causality within the
        # span falls out of the stored-position mask).  Write-before-read is
        # safe: a masked position is either an inactive slot (output unread)
        # or a catch-up position whose identical K/V is already arena-
        # resident, and PoolConfig.lookahead reserves the ring capacity the
        # up-to-W-past-frontier writes land in (DESIGN.md §9/§10).
        out = pops.paged_attention(q, new_cache["k"], new_cache["v"],
                                   block_tables, pos0 + w, ring_cap,
                                   window=cfg.window)
    else:
        k_hist = attnmod.paged_gather_kv(cache["k"], block_tables)
        v_hist = attnmod.paged_gather_kv(cache["v"], block_tables)
        hist_pos = attnmod.paged_slot_positions(pos0, ring_cap,
                                                k_hist.shape[1])
        out = attnmod.paged_prefill_attention(q, k_hist, v_hist, hist_pos,
                                              k, v, positions,
                                              window=cfg.window)
    out = tpmod.gather_heads(out, cfg.n_heads)
    mix = linear(p["wo"], out.reshape(b, w, -1))
    h = h + mix.astype(h.dtype)
    h2 = apply_norm(cfg.norm, h, lp["ln2"])
    y, _ = _ffn_apply(cfg, lp, h2, None, "ver")
    return h + y.astype(h.dtype), new_cache


def decode_verify_paged(cfg: ModelConfig, params: dict, caches: list,
                        tokens: jax.Array, pos0: jax.Array, active: jax.Array,
                        block_tables: jax.Array, ring_cap: jax.Array,
                        write_mask: jax.Array, scan: bool = True):
    """Score W tokens per slot in one batched step: tokens (S, W) starting
    at per-slot positions ``pos0`` -> (logits (S, W, V), new caches).

    The speculative-decoding workhorse (DESIGN.md §9): the target model
    verifies a draft's k proposals (W = k+1: last accepted token + k drafts)
    in a single fixed-shape dispatch, and the draft model uses the same step
    at W = 2 to catch up after an all-accept round.  Like
    ``decode_step_paged``, every churning input is a fixed-shape array, so
    the step compiles exactly once per (model, W).  Inactive slots run inert
    (embeddings zeroed, writes redirected to the null block).
    """
    if cfg.enc_dec:
        raise NotImplementedError(
            "paged serving does not support encoder-decoder archs")
    h = embed_tokens(cfg, params, tokens)
    if cfg.pos == "sinusoidal":
        d = h.shape[-1]
        table = sinusoidal_positions(caches_context(caches, cfg), d)
        positions = pos0[:, None] + jnp.arange(tokens.shape[1],
                                               dtype=jnp.int32)[None, :]
        h = h + table[jnp.minimum(positions, table.shape[0] - 1)].astype(h.dtype)
    h = jnp.where(active[:, None, None], h, 0)
    wmask = write_mask & active[:, None]

    def fn(mixer, lp, hh, cs):
        return layer_verify_paged(cfg, mixer, lp, hh, cs, pos0, active,
                                  block_tables, ring_cap, wmask)

    h, new_caches = _apply_layers(cfg, params, caches, h, fn, scan)
    h = apply_norm(cfg.norm, h, params["final_norm"])
    logits = tpmod.gather_cols(linear(params["lm_head"], h), cfg.vocab)
    return logits, new_caches


def layer_prefill_chunk(cfg: ModelConfig, mixer: str, lp: dict, h: jax.Array,
                        cache: dict, pos0: jax.Array, slot: jax.Array,
                        bt_row: jax.Array, ring_cap: jax.Array):
    """One layer over one request's prompt chunk h (1, C, d), reading and
    writing the paged pool at the request's slot / block-table row.

    ``pos0`` is the chunk's first absolute position; recurrent state is read
    from the slot arrays (zeros when pos0 == 0, i.e. a freshly admitted
    request on a recycled slot) and written back after the chunk.
    """
    b, c, d = h.shape
    hn = apply_norm(cfg.norm, h, lp["ln1"])
    new_cache = dict(cache)
    chunk_pos = pos0 + jnp.arange(c, dtype=jnp.int32)

    def slot_state(tree):
        return jax.tree.map(
            lambda a: jnp.where(pos0 > 0, a[slot], jnp.zeros_like(a[slot]))[None],
            tree)

    def store_state(tree, new):
        return jax.tree.map(lambda a, n: a.at[slot].set(n[0].astype(a.dtype)),
                            tree, new)

    if mixer == "attn":
        p = lp["attn"]
        positions = chunk_pos[None]
        if cfg.pos == "mrope":
            positions = jnp.broadcast_to(chunk_pos[None, None], (3, 1, c))
        q, k, v = _attn_qkv(cfg, p, hn, positions)
        k_hist = attnmod.paged_gather_kv(cache["k"], bt_row[None])
        v_hist = attnmod.paged_gather_kv(cache["v"], bt_row[None])
        hist_pos = attnmod.paged_slot_positions(pos0[None], ring_cap[None],
                                                k_hist.shape[1])
        out = attnmod.paged_prefill_attention(
            q, k_hist, v_hist, hist_pos, k, v, chunk_pos[None],
            window=cfg.window)
        out = tpmod.gather_heads(out, cfg.n_heads)
        mix = linear(p["wo"], out.reshape(b, c, -1))
        block_size = cache["k"].shape[2]
        pb, off = attnmod.paged_write_indices(chunk_pos, ring_cap, bt_row,
                                              block_size)
        new_cache["k"] = attnmod.paged_write(cache["k"], pb, off, k[0])
        new_cache["v"] = attnmod.paged_write(cache["v"], pb, off, v[0])
    elif mixer == "mla":
        mix, mc = mlamod.mla_prefill_chunk(lp["mla"], hn, cfg.mla,
                                           cache["mla"], pos0, slot)
        new_cache["mla"] = mc
    elif mixer == "rwkv":
        rwkv_st0 = slot_state(cache["rwkv"])
        mix, s_new = rwkvmod.time_mix(lp["tm"], hn, n_heads=cfg.n_heads,
                                      head_dim=cfg.hd, return_state=True,
                                      state=rwkv_st0)
        rwkv_st = rwkvmod.RWKVState(
            s=s_new, x_prev_tm=hn[:, -1].astype(rwkv_st0.x_prev_tm.dtype),
            x_prev_cm=rwkv_st0.x_prev_cm)
    elif mixer == "rglru":
        st0 = slot_state(cache["rglru"])
        mix, st = rglrumod.rglru_block(lp["rglru"], hn, return_state=True,
                                       state=st0)
        new_cache["rglru"] = store_state(cache["rglru"], st)
    else:
        raise ValueError(mixer)
    h = h + mix.astype(h.dtype)
    h2 = apply_norm(cfg.norm, h, lp["ln2"])
    if mixer == "rwkv":
        y = rwkvmod.channel_mix(lp["cm"], h2, rwkv_st0.x_prev_cm)
        rwkv_st = rwkvmod.RWKVState(
            s=rwkv_st.s, x_prev_tm=rwkv_st.x_prev_tm,
            x_prev_cm=h2[:, -1].astype(rwkv_st.x_prev_cm.dtype))
        new_cache["rwkv"] = store_state(cache["rwkv"], rwkv_st)
    else:
        y, _ = _ffn_apply(cfg, lp, h2, None, "pfc")
    return h + y.astype(h.dtype), new_cache


def prefill_chunk_paged(cfg: ModelConfig, params: dict, caches: list,
                        tokens: jax.Array, pos0: jax.Array, slot: jax.Array,
                        bt_row: jax.Array, ring_cap: jax.Array,
                        scan: bool = True):
    """One prompt chunk for one request: tokens (1, C) starting at absolute
    position ``pos0`` -> (last-token logits (1, V), new caches).  Interleaves
    with decode steps in the engine loop (chunked prefill)."""
    if cfg.enc_dec:
        raise NotImplementedError(
            "paged serving does not support encoder-decoder archs")
    h = embed_tokens(cfg, params, tokens)
    if cfg.pos == "sinusoidal":
        d = h.shape[-1]
        table = sinusoidal_positions(caches_context(caches, cfg), d)
        c = tokens.shape[1]
        h = h + jax.lax.dynamic_slice_in_dim(table, pos0, c, 0)[None].astype(h.dtype)

    def fn(mixer, lp, hh, cs):
        return layer_prefill_chunk(cfg, mixer, lp, hh, cs, pos0, slot,
                                   bt_row, ring_cap)

    h, new_caches = _apply_layers(cfg, params, caches, h, fn, scan)
    h = apply_norm(cfg.norm, h, params["final_norm"])
    logits = tpmod.gather_cols(linear(params["lm_head"], h[:, -1]), cfg.vocab)
    return logits, new_caches


def caches_context(caches: list, cfg: ModelConfig) -> int:
    """Max positional extent needed for sinusoidal decode tables."""
    for cs in caches:
        leaves = jax.tree.leaves(cs)
        for leaf in leaves:
            if leaf.ndim >= 3:
                return max(2048, leaf.shape[2])
    return 2048
