"""Pallas TPU kernels (compiled on a TPU, interpreted on the CPU).

  odc_gather       one-sided remote-DMA ring *gather* (paper Fig. 5 left);
                   ``odc_gather_layers`` chains L rings with no
                   inter-layer barrier — the cross-layer prefetch behind
                   ``schedule='overlap'``
  odc_scatter      one-sided remote-DMA ring *scatter-accumulate* (right);
                   ``odc_scatter_accumulate_layers`` is its cross-layer
                   twin (async gradient pushes, no inter-layer barrier)
  gather_matmul    ODC gather fused with the consumer matmul — the §6.1
                   "overlap communication with computation" realized at
                   kernel level (collective-matmul pattern)
  flash_attention  blockwise attention: causal, sliding-window, softcap
  ssd_scan         Mamba2 SSD chunked scan

Each kernel has a jit wrapper in ``repro.kernels.ops`` and a pure-jnp
oracle in ``repro.kernels.ref``.  Backends in the
``repro.core.backend`` registry expose these as their hardware
realization (``CommBackend.kernel_gather`` /
``kernel_scatter_accumulate``, gated on ``has_kernels``); the jnp
primitives in ``repro.core.odc`` remain the numerical oracles.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def interpret_mode(interpret=None) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode — decided here for
    every kernel of the package.  ``None`` follows the default backend:
    compiled on a TPU, interpreted anywhere else.  Interpret mode is
    refused on a TPU, so no path there falls back to the interpreter.
    ``False`` elsewhere is for compiling against a described TPU topology
    (ahead-of-time compile tests)."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU backend")
    return bool(interpret)


def remote_interpret(interpret=None):
    """``pallas_call(interpret=...)`` for the remote-DMA ring kernels: the
    TPU interpreter (remote copies, semaphores across devices) where
    :func:`interpret_mode` says interpret, else compiled."""
    return pltpu.InterpretParams() if interpret_mode(interpret) else False
