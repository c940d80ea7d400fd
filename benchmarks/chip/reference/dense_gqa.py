"""Plain float32 reference for the dense GQA decoder (Qwen3, InternLM2).

Imports nothing of the system under test.  It holds two things:

* ``init_weights``: the benchmark's own weights from the seed, made on the
  device in one jitted call, in the layout the serving program takes
  (``{"embed", "layers": [stacked layer dict], "final_norm", "lm_head"}``).
  The reference reads the same arrays, so neither side takes anything the
  other made.
* ``forward_logits``: the full causal forward pass in ``jax.numpy`` with
  every contraction at ``Precision.HIGHEST`` (true float32 on a TPU):
  pre-norm RMSNorm (eps 1e-6), Q/K RMSNorm over the head dimension when the
  model has it (Qwen3), rotary embedding in the half-split convention, GQA
  softmax attention (query head ``i`` reads KV head ``i // (H / KV)``),
  SwiGLU with ``wi`` holding gate then up, a final RMSNorm and an untied
  ``lm_head``.

Departures from the published models: random weights, norm scales of one,
and no biases (neither model has any in its linears).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole number (``PRNGKey`` truncates past
    32 bits, which would give many seeds one key)."""
    import numpy as np
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _layer(m: dict, init: dict, key, u) -> dict:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"],
                       m["d_ff"])
    res = (2 * m["n_layers"]) ** -0.5
    # attention scores have standard deviation about ``qk_gain`` ** 2
    g = init.get("qk_gain", 1.0)
    qk = 1.0 if m.get("qk_norm") else g
    k = jax.random.split(key, 7)
    wq = _normal(k[0], (d, h * hd), qk * d ** -0.5)
    wk = _normal(k[1], (d, kv * hd), qk * d ** -0.5)
    rec = init.get("recency", 0.0)
    if rec:
        # every head's query and key share one component along the shared
        # direction ``u`` of the residual stream, the same for both: under
        # RoPE its part of a score falls with the distance between the two
        # positions, so each token attends most to the tokens just before it
        a = jax.random.normal(k[6], (kv, hd), jnp.float32)
        a = a * (hd / jnp.sum(a * a, -1, keepdims=True)) ** 0.5
        gain = (rec * hd ** -0.5) ** 0.5 / init.get("shared", 1.0)
        un = u / jnp.dot(u, u)
        wk = wk + gain * jnp.outer(un, a.reshape(-1))
        wq = wq + gain * jnp.outer(un, jnp.repeat(a, h // kv, 0).reshape(-1))
    attn = {"wq": wq, "wk": wk,
            "wv": _normal(k[2], (d, kv * hd), d ** -0.5),
            "wo": _normal(k[3], (h * hd, d),
                          init.get("attn_gain", 1.0) * res * (h * hd) ** -0.5)}
    if m.get("qk_norm"):
        attn["q_norm"] = jnp.full((hd,), g, jnp.float32)
        attn["k_norm"] = jnp.full((hd,), g, jnp.float32)
    return {"ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": attn,
            "mlp": {"wi": _normal(k[4], (d, 2 * f), d ** -0.5),
                    "wo": _normal(k[5], (f, d), res * f ** -0.5)}}


def init_weights(m: dict, seed: int, init: dict | None = None) -> dict:
    """Float32 weights for model sizes ``m`` from ``seed``, on the device.
    ``init`` may set ``qk_gain``: the scale of queries and keys (through
    ``wq``/``wk``, or the Q/K norm scales where the model has them);
    ``attn_gain``: the scale of the attention's output projection;
    ``shared`` and ``recency``: a direction common to every embedding, and
    the part of a score at distance 0 that it adds through every query and
    key.  A random model has no sense of order: without ``recency`` a
    decode step that forgets its tokens' KV changes its next tokens no more
    than quantization does."""
    init = init or {}

    def make(key):
        k_emb, k_head, k_layers, k_u = jax.random.split(key, 4)
        d = m["d_model"]
        # ``shared``: the weight of one direction ``u`` common to every
        # token's embedding (``recency`` reads it); the head does not see it
        al = init.get("shared", 0.0)
        u = jax.random.normal(k_u, (d,), jnp.float32)
        embed = ((1 - al * al) ** 0.5 * _normal(k_emb, (m["vocab"], d), 1.0)
                 + al * u)
        head = _normal(k_head, (d, m["vocab"]), d ** -0.5)
        head = head - jnp.outer(u / jnp.dot(u, u), u @ head)
        layers = jax.vmap(lambda k: _layer(m, init, k, u))(
            jax.random.split(k_layers, m["n_layers"]))
        return {"embed": embed,
                "layers": [layers],
                "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
                "lm_head": head}
    return jax.jit(make)(seed_key(seed))


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, theta):
    """x (S, heads, hd) at positions 0..S-1, half-split rotation."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(m: dict, h, lp):
    s = h.shape[0]
    nh, kv, hd = m["n_heads"], m["n_kv"], m["head_dim"]
    a = lp["attn"]
    x = _rms(h, lp["ln1"]["scale"])
    q = jnp.dot(x, a["wq"], precision=HI).reshape(s, nh, hd)
    k = jnp.dot(x, a["wk"], precision=HI).reshape(s, kv, hd)
    v = jnp.dot(x, a["wv"], precision=HI).reshape(s, kv, hd)
    if "q_norm" in a:
        q, k = _rms(q, a["q_norm"]), _rms(k, a["k_norm"])
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    q = q.reshape(s, kv, nh // kv, hd)
    sc = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI).reshape(s, nh * hd)
    h = h + jnp.dot(o, a["wo"], precision=HI)
    x = _rms(h, lp["ln2"]["scale"])
    gate, up = jnp.split(jnp.dot(x, lp["mlp"]["wi"], precision=HI), 2, -1)
    return h + jnp.dot(jax.nn.silu(gate) * up, lp["mlp"]["wo"], precision=HI)


def forward_logits(m: dict, w: dict, tokens, rows):
    """Logits (len(rows), vocab) at positions ``rows`` of one sequence
    ``tokens`` (S,).  Positions past the last row may hold padding: the
    mask is causal, so they change nothing before them."""
    h = w["embed"][tokens]
    h, _ = jax.lax.scan(lambda hh, lp: (_block(m, hh, lp), None), h,
                        w["layers"][0])
    h = _rms(h[rows], w["final_norm"]["scale"])
    return jnp.dot(h, w["lm_head"], precision=HI)
