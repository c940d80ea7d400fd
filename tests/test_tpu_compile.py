"""The serving kernels compile for a TPU v5e (ahead of time, no chip).

Interpret mode cannot show what the chip's compiler refuses (casts, shifts,
lane reshapes, block shapes); these tests hand the main-path kernels to it
at qwen3-0.6b widths — d_model 1024 (1020 after outlier exclusion, the
overlapped Alg. 5 case), d_ff 3072, head_dim 128, 8 KV heads, 16-token
blocks — for a described ``v5e:2x2`` topology, and require a compiled
``tpu_custom_call`` in each program.  The topology is described inside a
module-scoped fixture, so every test worker collects the same tests and
only the one that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hadamard import largest_pow2_leq
from repro.core.packing import packed_rows
from repro.kernels.paged_attention.paged import paged_attention_pallas
from repro.kernels.qmatmul.qmatmul import rht_quantized_matmul_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the persistent
    # cache without one, so keep the cache off around these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _qlinear_shapes(sharding, n, d, c, bits, experts=None):
    lead = () if experts is None else (experts,)
    d_hat = largest_pow2_leq(d)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                  sharding=sharding)
    return (spec(lead + (n, d), jnp.float32),
            spec(lead + (packed_rows(d, bits), c), jnp.uint8),
            spec(lead + (c,), jnp.float16),
            spec((d_hat,), jnp.float32), spec((d_hat,), jnp.float32))


@pytest.mark.parametrize("n,d,c,bits", [
    (8, 1024, 2048, 4),     # wq, power-of-2 d, two codes per byte
    (8, 1020, 6144, 3),     # fused gate|up wi, overlapped d, byte codes
    (8, 3062, 1024, 2),     # mlp wo, overlapped d, four codes per byte
])
def test_fused_qmatmul_compiles(one_chip, n, d, c, bits):
    shapes = _qlinear_shapes(one_chip, n, d, c, bits)
    if d == largest_pow2_leq(d):
        shapes = shapes[:4]

    def fn(x, p, r, s1, s2=None):
        return rht_quantized_matmul_pallas(x, p, r, s1, s2, bits=bits, d=d)

    assert _compile(fn, *shapes).count('"tpu_custom_call"') == 1


def test_grouped_fused_qmatmul_compiles(one_chip):
    """The MoE form: the fused kernel vmapped over experts."""
    e, d, c, bits = 4, 1020, 1024, 4

    def fn(x, p, r, s1, s2):
        return jax.vmap(lambda xe, pe, re: rht_quantized_matmul_pallas(
            xe, pe, re, s1, s2, bits=bits, d=d))(x, p, r)

    hlo = _compile(fn, *_qlinear_shapes(one_chip, 8, d, c, bits, experts=e))
    assert '"tpu_custom_call"' in hlo


def _paged_custom_calls(sharding, b, w, h, kv, hd, bs, n_blocks, mb,
                        dtype) -> list[str]:
    """The ``tpu_custom_call`` instructions of the paged-attention kernel
    compiled at these shapes."""
    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    hlo = _compile(
        lambda *a: paged_attention_pallas(*a),
        spec((b, w, h, hd), jnp.float32),
        spec((n_blocks, kv, bs, hd), dtype),
        spec((n_blocks, kv, bs, hd), dtype),
        spec((b, mb), jnp.int32), spec((b,), jnp.int32),
        spec((b,), jnp.int32))
    return [line for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles(one_chip, w, dtype):
    """Decode (W=1) and speculative verify (W=k+1) over f32 and bf16
    arenas laid out (N, KV, bs, hd)."""
    calls = _paged_custom_calls(one_chip, 8, w, 16, 8, 128, 16, 73, 9, dtype)
    assert len(calls) == 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_compiles_at_batch_cell(one_chip, dtype):
    """The batch cell's decode call: 64 slots, 16/8 heads of 128, 16-token
    blocks, a 56-entry table over 2,800 blocks.  Its page buffers (P pages
    of K and V, twice) fit VMEM for the bf16 arena and for the f32 one,
    and the kernel keeps the name the trace reduction keys on."""
    calls = _paged_custom_calls(one_chip, 64, 1, 16, 8, 128, 16, 2800, 56,
                                dtype)
    assert len(calls) == 1
    assert calls[0].lstrip().startswith("%paged_attention")
