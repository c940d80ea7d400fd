"""Pallas TPU kernels for RaanA's compute hot-spots.

Five kernels — three are TPU-native adaptations of stages the paper runs on
CPU/GPU (DESIGN.md §3), the other two (flash_attention, paged_attention)
are the beyond-paper inference-efficiency levers:

  * ``hadamard``        — RHT as two MXU matmuls per VMEM-resident tile
                          (Kronecker-factorized FWHT; Hadacore's tensor-core
                          idea re-thought for the 128x128 systolic array).
  * ``qmatmul``         — fused unpack -> dequant -> GEMM with the Alg. 3
                          rescale/z epilogue; codes cross HBM packed.
  * ``rabitq_quant``    — per-column candidate-sweep code search + LS rescale.
  * ``flash_attention`` — fused online-softmax forward (EXPERIMENTS.md §Perf).
  * ``paged_attention`` — flash-decoding over the serving engine's block
                          arena, block table chased in-kernel (DESIGN.md §10).

Every ``ops.py`` wrapper dispatches: real ``pallas_call`` on TPU, and a
pure-jnp reference path elsewhere, where interpret mode would be needlessly
slow.  Every Pallas entry point compiles for the chip unless its caller
passes ``interpret=True`` (tests, and a path forced to the kernel off-TPU).
"""
import jax


def default_interpret() -> bool:
    """Whether a dispatcher's kernel path runs through the Pallas
    interpreter: only off-TPU, where forcing the kernel path is the only
    way to reach it."""
    return jax.default_backend() != "tpu"
