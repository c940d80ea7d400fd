"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the window finished (drawn from the seed, the one with the most
served tokens always in it) is run through the plain float32 reference once
each: the prompt followed by the served tokens.  At every position where the
program served a token, the gap is how far that token's reference logit
lies below the reference's best.  The served tokens are greedy, so a sound
run's gaps come from the quantized weights' error alone.

One number is compared with its limit from ``limits/<cell>.json``:
``gap_mean``, the mean gap over every compared token, which a quantizer
that keeps fewer bits than the configuration states fails, as do tokens
altered where they are sampled and a decode step that forgets its KV.
The widest gap, ``gap_max``, is printed beside it but not compared: sound
runs and the control read it too close together (``PERF.md``).
"""
from __future__ import annotations

import functools
import math

import numpy as np

NUMBERS = ("gap_mean",)


def bucket(n: int, to: int = 128) -> int:
    return -(-n // to) * to


@functools.lru_cache(maxsize=8)
def _ref_fn(ref, model_items):
    import jax
    m = dict(model_items)
    return jax.jit(lambda w, x, rows: ref.forward_logits(m, w, x, rows))


def gaps_for(ref, model: dict, weights, seqs, seq_len: int,
             n_rows: int, chooser=None):
    """Per sequence ``(prompt, served)``: the reference's gap at each
    served token, or, with ``chooser``, at the token that
    ``chooser(prompt, served, rows)`` puts first at each position (the
    control's reading).  Sequences are padded to ``seq_len`` tokens and
    ``n_rows`` positions, so one compiled program serves a cell."""
    import jax.numpy as jnp
    fn = _ref_fn(ref, tuple(sorted(model.items())))
    out = []
    for prompt, served in seqs:
        served = np.asarray(served, np.int64)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        rows = len(prompt) - 1 + np.arange(len(served))
        x = np.zeros(seq_len, np.int32)
        x[:len(seq)] = seq
        r = np.zeros(n_rows, np.int32)
        r[:len(rows)] = rows
        lg = np.asarray(fn(weights, jnp.asarray(x), jnp.asarray(r)))
        lg = lg[:len(rows)].astype(np.float64)
        pick = served if chooser is None else np.asarray(
            chooser(prompt, served, rows))
        out.append(lg.max(-1) - lg[np.arange(len(rows)), pick])
    return out


def numbers(gaps) -> dict:
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    if allg.size == 0:
        return {"gap_max": math.inf, "gap_mean": math.inf, "tokens": 0}
    return {"gap_max": float(allg.max()), "gap_mean": float(allg.mean()),
            "tokens": int(allg.size)}


def judge(nums: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and, per number, ``(name, value, limit)``: each number
    must be at or under its limit.  No tokens compared is not correct."""
    rows = [(k, nums[k], limits[k]) for k in NUMBERS]
    ok = nums.get("tokens", 0) > 0 and all(
        math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
