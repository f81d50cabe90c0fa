"""Mamba2 SSD (state-space duality) chunked scan Pallas kernel.

Grid: (batch, heads, chunks) — chunks iterated sequentially per core with
the inter-chunk recurrent state (p, n) carried in VMEM scratch; each chunk
step computes the intra-chunk (Q, Q) attention-like block on the MXU plus
the off-diagonal contribution through the carried state (the "duality").

BlockSpecs tile per (batch row, head, chunk): x (1, Q, 1, p), dt/A
broadcast per head, B/C (1, Q, n) for the head's group.  VMEM working set
is O(Q·p + Q·n + p·n + Q²) — Q (the chunk length) is the tiling knob.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, num_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)      # (Q, p)
    dt = dt_ref[0, :, 0].astype(jnp.float32)    # (Q,)
    a = a_ref[0].astype(jnp.float32)            # scalar A for this head
    bmat = b_ref[0, :, 0].astype(jnp.float32)   # (Q, n)
    cmat = c_ref[0, :, 0].astype(jnp.float32)   # (Q, n)

    xd = x * dt[:, None]
    adt = a * dt                                 # (Q,)
    acum = jnp.cumsum(adt)                       # (Q,)

    # intra-chunk: L[q, t] = exp(acum_q - acum_t) for q >= t
    Q = x.shape[0]
    lmat = jnp.exp(acum[:, None] - acum[None, :])
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lmat = jnp.where(row >= col, lmat, 0.0)
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot(scores * lmat, xd, preferred_element_type=jnp.float32)

    # off-diagonal: prior state flowing into this chunk
    prior = state_ref[...]                       # (p, n)
    y += jnp.exp(acum)[:, None] * jax.lax.dot_general(
        cmat, prior, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # chunk state update: state = decay * prior + sum_t B_t (decay_to_end_t x_t)
    decay_end = jnp.exp(acum[-1] - acum)         # (Q,)
    new_contrib = jax.lax.dot_general(
        xd * decay_end[:, None], bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (p, n)
    state_ref[...] = prior * jnp.exp(acum[-1]) + new_contrib

    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_ref[...].astype(state_out_ref.dtype)


def ssd_scan_pallas(x, dt, A, Bm, Cm, *, chunk: int, interpret=None):
    """Same contract as ``repro.models.ssm.ssd_chunked`` (without initial
    state): x (b, s, h, p); dt (b, s, h); A (h,); Bm/Cm (b, s, g, n) with
    h % g == 0.  Returns (y (b, s, h, p), final_state (b, h, p, n))."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    Q = min(chunk, s)
    assert s % Q == 0, (s, Q)
    nc = s // Q

    grid = (b, h, nc)
    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, Q, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, Q, 1, n),
                         lambda ib, ih, ic: (ib, ic, ih // rep, 0)),
            pl.BlockSpec((1, Q, 1, n),
                         lambda ib, ih, ic: (ib, ic, ih // rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, dt, A, Bm, Cm)
    return y, state
