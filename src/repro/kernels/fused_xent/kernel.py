"""Fused softmax cross-entropy Pallas kernel.

Computes nll[i] = logsumexp_j(h[i]·W[:,j]) − h[i]·W[:,label[i]] WITHOUT
materializing the (tokens, vocab) logits: the grid streams vocab tiles
(minor axis) through VMEM, maintaining an online (max, sumexp, gold)
accumulator per token tile.  This is the ISGD hot spot — a loss is needed
every iteration (and up to ``stop`` more inside the subproblem), and at
gemma3's 262k vocab the naive path writes B·S·V logits to HBM twice.

Tiling: token tile ``bn`` × vocab tile ``bv`` (both 128-aligned for the MXU);
the h tile (bn, d) stays resident in VMEM across the vocab sweep
(index_map ignores the vocab grid coordinate).  Per-token vectors (labels,
the nll output and the running max/sumexp/gold) are ``(bn, 1)`` columns:
Mosaic tiles the last two dims of every block, and a 1-D ``(bn,)`` block
gets an HBM layout that does not match the kernel's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import divisor_tile


def _xent_kernel(h_ref, w_ref, label_ref, out_ref, m_ref, s_ref, g_ref,
                 *, bv: int, vocab_size: int):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        s_ref[...] = jnp.zeros_like(s_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    h = h_ref[...].astype(jnp.float32)            # (bn, d)
    w = w_ref[...].astype(jnp.float32)            # (d, bv)
    logits = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    v0 = vi * bv
    col = v0 + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < vocab_size, logits, -1e30)

    m_prev = m_ref[...]                            # (bn, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    s_ref[...] = s_ref[...] * corr + jnp.sum(jnp.exp(logits - m_new), axis=1,
                                             keepdims=True)
    m_ref[...] = m_new

    hit = col == label_ref[...]                    # (bn, bv) vs (bn, 1)
    g_ref[...] = g_ref[...] + jnp.sum(jnp.where(hit, logits, 0.0), axis=1,
                                      keepdims=True)

    @pl.when(vi == nv - 1)
    def _finish():
        out_ref[...] = jnp.log(s_ref[...]) + m_ref[...] - g_ref[...]


@functools.partial(jax.jit, static_argnames=("vocab_size", "bn", "bv", "interpret"))
def fused_xent(h, w, labels, *, vocab_size: int, interpret: bool, bn: int = 256,
               bv: int = 512):
    """h: (N, d); w: (d, Vp); labels: (N,) -> nll (N,) f32.  ``interpret``
    runs the kernel through the Pallas interpreter instead of Mosaic."""
    N, d = h.shape
    Vp = w.shape[1]
    # requested tiles are upper bounds: training bodies hand us whatever
    # B·S / padded-vocab the config dictates, so shrink to dividing tiles
    bn = divisor_tile(N, bn)
    bv = divisor_tile(Vp, bv)
    grid = (N // bn, Vp // bv)
    col = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    nll = pl.pallas_call(
        functools.partial(_xent_kernel, bv=bv, vocab_size=vocab_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            col,
        ],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(h, w, labels.reshape(N, 1).astype(jnp.int32))
    return nll.reshape(N)
