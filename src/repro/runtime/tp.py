"""Tensor-parallel serving over ``shard_map`` (DESIGN.md §11).

One engine drives every chip of a ``("data", "model")`` mesh: the paged
serving steps run inside an explicit ``shard_map`` whose in/out specs are
built here.  The partitioning scheme is chosen for the *quantized*
representation (packed codes + f16 side info), whose rows are entangled by
the randomized Hadamard transform — a RaanA weight cannot be row-sharded
without re-quantizing per shard, but its output columns are mutually
independent (each column owns its packed codes, its ``rescale`` entry and
its ``w_out`` outlier column).  So every sharded weight is **column-
(output-) sharded** and the TP boundary is an ``all_gather`` of disjoint
output slices, never a ``psum`` of partial products:

  * attention ``wq``/``wk``/``wv`` shard by head over ``"model"`` (and the
    KV block arena shards its head axis to match); ``wo`` stays replicated
    and consumes the head-gathered attention output,
  * the fused gate|up ``wi`` (dense, MoE expert, and shared-expert) shards
    by FFN column — with a placement-time column permutation to per-shard
    ``[gate_i | up_i]`` blocks so the local ``split(gu, 2)`` stays correct —
    and ``wo`` stays replicated behind a hidden-state gather,
  * ``lm_head`` shards the vocab and the logits gather once per step.

Replicating the row-parallel weights costs memory Megatron would shard, but
buys the property the serving tests pin: every shard computes bit-identical
per-column math to the single-device engine (no cross-shard float
reduction anywhere), so greedy outputs are token-identical at every TP
degree and ONE quantization artifact serves all of them.

A dimension that does not divide the ``"model"`` axis degrades to
replication (``sharding._fit``), and attention shards only when *both*
``n_heads`` and ``n_kv`` divide — ``wq``/``wk``/``wv`` and the arena must
agree on the GQA group ratio.  Everything dynamic that the scheduler churns
(block tables, positions, active masks) plus all host-side ownership state
(allocator, prefix cache) stays replicated/host-side; the gather helpers
below are shape-driven no-ops whenever the local dim is already full, so
the single-device engine is literally the TP=1 special case of the same
code path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import _fit

AXIS = "model"
_R = P()


# ------------------------------------------------------------------- mesh


def default_mesh() -> Mesh:
    """The trivial (1, 1) serving mesh — TP=1 as the degenerate case of the
    sharded path, so the engine has exactly one code path."""
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


# ------------------------------------------------------------------- plan


@dataclasses.dataclass(frozen=True)
class TPPlan:
    """Which weight families actually shard at this TP degree (the rest
    replicate).  Attention is all-or-nothing: ``wq``/``wk``/``wv`` and the
    KV arena shard together or not at all, so the GQA group ratio is the
    same on every shard."""
    tp: int
    attn: bool       # wq/wk/wv by head + KV arena head axis
    ffn: bool        # dense glu/gelu wi by FFN column
    moe: bool        # expert wi by per-expert FFN column
    shared: bool     # shared-expert swi by FFN column
    lm_head: bool    # vocab columns (logits gathered once per step)

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


def plan_for(cfg, tp: int) -> TPPlan:
    moe = cfg.moe
    return TPPlan(
        tp=tp,
        attn=tp > 1 and cfg.n_heads % tp == 0 and cfg.n_kv % tp == 0,
        ffn=tp > 1 and cfg.moe is None and cfg.d_ff % tp == 0,
        moe=tp > 1 and moe is not None and moe.d_ff_expert % tp == 0,
        shared=(tp > 1 and moe is not None and moe.n_shared > 0
                and (moe.d_ff_expert * moe.n_shared) % tp == 0),
        lm_head=tp > 1 and cfg.vocab % tp == 0)


# ------------------------------------------- trace-time gather helpers


def gather_heads(x: jax.Array, full_heads: int) -> jax.Array:
    """(..., H_loc, hd) -> (..., H, hd): concatenate per-shard head slices
    over ``"model"``.  Shape-driven: a no-op when the heads are already
    full (TP=1 or replication fallback), so callers need no TP flag."""
    if x.shape[-2] == full_heads:
        return x
    return jax.lax.all_gather(x, AXIS, axis=x.ndim - 2, tiled=True)


def gather_cols(y: jax.Array, full_dim: int) -> jax.Array:
    """(..., c_loc) -> (..., c): concatenate per-shard column slices over
    ``"model"`` (FFN hidden states, logits).  No-op when already full."""
    if y.shape[-1] == full_dim:
        return y
    return jax.lax.all_gather(y, AXIS, axis=y.ndim - 1, tiled=True)


def in_dim(w) -> int:
    """Full input width of a 2-D weight (array or QuantizedLinear — both
    expose ``.shape`` as the logical (d_in, d_out))."""
    return w.shape[0]


# ----------------------------------------------- param specs + placement

# column-sharded weight keys, gated by the plan flag that owns them; the
# quantized-leaf fields of a sharded weight that slice along the column
# axis (everything else — signs, outlier indices, mean column — replicates)
_SHARDED_FIELDS = {"packed", "rescale", "w_out"}
_REPLICATED_FIELDS = {"signs1", "signs2", "mean_col", "out_idx", "keep_idx"}


def _path_names(path) -> list[str]:
    return [str(getattr(e, "key", getattr(e, "name", ""))) for e in path]


def _decision(plan: TPPlan, names: list[str]):
    """(shard, glu_permute) for the weight node owning this leaf."""
    if "attn" in names and any(k in names for k in ("wq", "wk", "wv")):
        return plan.attn, False
    if "swi" in names:
        return plan.shared, True
    if "wi" in names:
        if "moe" in names:
            return plan.moe, True
        if "mlp" in names:
            # permute only for fused gate|up layouts — prepare_params
            # drops the flag for plain-gelu (whisper) archs
            return plan.ffn, True
    if "lm_head" in names:
        return plan.lm_head, False
    return False, False


def _glu_perm(two_f: int, tp: int) -> np.ndarray:
    """Column permutation taking a fused [gate | up] layout (2f columns) to
    interleaved per-shard [gate_i | up_i] blocks, so shard i's local
    ``split(gu, 2, axis=-1)`` yields exactly gate/up columns
    [i*f/tp, (i+1)*f/tp) and the gathered hidden state lands in natural
    column order.  Exact for quantized leaves too: packed codes, rescale
    and outlier rows are all per-column."""
    f = two_f // 2
    fl = f // tp
    return np.concatenate([
        np.concatenate([np.arange(i * fl, (i + 1) * fl),
                        f + np.arange(i * fl, (i + 1) * fl)])
        for i in range(tp)])


def _leaf_spec(plan: TPPlan, names: list[str], leaf, mesh: Mesh):
    """(PartitionSpec, permute_cols) for one param leaf."""
    nd = getattr(leaf, "ndim", 0)
    shard, glu = _decision(plan, names)
    if not shard or nd == 0:
        return P(*([None] * nd)), False
    field = names[-1]
    if field in _REPLICATED_FIELDS:
        return P(*([None] * nd)), False
    # raw weight arrays and the column-sliced quantized fields all shard
    # their last (output-column) axis
    spec = _fit(P(*([None] * (nd - 1)), AXIS), leaf.shape, mesh)
    if spec[-1] is None:      # _fit dropped it: dim doesn't divide
        return spec, False
    return spec, glu


def prepare_params(cfg, params: Any, mesh: Mesh):
    """Shard-place a (possibly quantized) param tree for TP serving.

    Returns ``(placed_params, spec_list)`` where ``spec_list`` is ordered
    like ``jax.tree.flatten(params)`` — the in_specs the engine's
    ``shard_map`` wrapper uses.  Weights that shard get ``device_put`` with
    a column sharding (after the gate/up interleaving permutation for fused
    glu ``wi``); everything else replicates across the whole mesh.
    """
    tp = int(mesh.shape[AXIS])
    plan = plan_for(cfg, tp)
    glu_ffn = cfg.ffn_kind() != "gelu"   # fused gate|up wi (glu/moe archs)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs, placed = [], []
    for path, leaf in flat:
        names = _path_names(path)
        spec, permute = _leaf_spec(plan, names, leaf, mesh)
        if permute and glu_ffn:
            perm = _glu_perm(int(leaf.shape[-1]), tp)
            leaf = jnp.take(leaf, jnp.asarray(perm), axis=-1)
        specs.append(spec)
        placed.append(jax.device_put(leaf, NamedSharding(mesh, spec)))
    return jax.tree_util.tree_unflatten(treedef, placed), specs


def cache_spec_list(caches: Any, mesh: Mesh, plan: TPPlan) -> list[P]:
    """Specs for the pool cache tree, ordered like its flatten order: the
    attention block arenas (n_j, N, KV, bs, hd) shard their KV-head axis
    when the plan shards attention; per-slot recurrent/MLA state and
    everything else replicates (block tables never reach device state —
    they are step *arguments*, replicated like the rest of the scheduler's
    churn)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(caches)
    specs = []
    for path, leaf in flat:
        names = _path_names(path)
        if plan.attn and leaf.ndim == 5 and names and names[-1] in ("k", "v"):
            specs.append(_fit(P(None, None, AXIS, None, None),
                              leaf.shape, mesh))
        else:
            specs.append(P(*([None] * leaf.ndim)))
    return specs


def place(tree: Any, spec_list: list[P], mesh: Mesh):
    """device_put each leaf of ``tree`` with its spec (flatten order)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    placed = [jax.device_put(l, NamedSharding(mesh, s))
              for l, s in zip(leaves, spec_list)]
    return jax.tree_util.tree_unflatten(treedef, placed)


# ------------------------------------------------------ shard_map wrapper


def sharded_call(core: Callable, mesh: Mesh, pspecs: list[P],
                 cspecs: list[P]) -> Callable:
    """Wrap ``core(params, caches, *arrays) -> (out, new_caches)`` in a
    ``shard_map`` over ``mesh``.

    Trees are flattened at the boundary so in/out specs are plain tuples of
    ``PartitionSpec`` (quantized param trees carry static dataclass
    metadata that spec-tree prefix matching would trip over).  All step
    arguments and the output are replicated; caches go in and come out
    under the same specs, so jit donation of the pool buffers survives the
    wrapper.  ``check_vma=False``: the output IS replicated by construction
    (every shard finishes with fully-gathered activations) but shard_map
    cannot prove it through ``all_gather``-of-disjoint-slices."""
    psp, csp = tuple(pspecs), tuple(cspecs)

    def call(params, caches, *arrays):
        pl, _ = jax.tree_util.tree_flatten(params)
        cl, ctd = jax.tree_util.tree_flatten(caches)
        ptd = jax.tree_util.tree_structure(params)

        def body(pl_, cl_, arrs):
            p = jax.tree_util.tree_unflatten(ptd, pl_)
            c = jax.tree_util.tree_unflatten(ctd, cl_)
            out, nc = core(p, c, *arrs)
            return out, tuple(jax.tree_util.tree_flatten(nc)[0])

        out, ncl = jax.shard_map(
            body, mesh=mesh, in_specs=(psp, csp, _R),
            out_specs=(_R, csp), check_vma=False)(
                tuple(pl), tuple(cl), tuple(arrays))
        return out, jax.tree_util.tree_unflatten(ctd, list(ncl))

    return call


def sharded_cache_op(core: Callable, mesh: Mesh, cspecs: list[P]) -> Callable:
    """Like ``sharded_call`` for cache-only ops (the copy-on-write block
    clone): ``core(caches, *arrays) -> new_caches`` under the cache specs."""
    csp = tuple(cspecs)

    def call(caches, *arrays):
        cl, ctd = jax.tree_util.tree_flatten(caches)

        def body(cl_, arrs):
            nc = core(jax.tree_util.tree_unflatten(ctd, cl_), *arrs)
            return tuple(jax.tree_util.tree_flatten(nc)[0])

        ncl = jax.shard_map(body, mesh=mesh, in_specs=(csp, _R),
                            out_specs=csp, check_vma=False)(tuple(cl),
                                                            tuple(arrays))
        return jax.tree_util.tree_unflatten(ctd, list(ncl))

    return call
