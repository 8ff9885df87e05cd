"""Blocked (flash-style) causal attention Pallas kernel with optional
sliding window.

Grid: (batch·heads, q_tiles, k_tiles) with k minor.  Per (bh, q) tile the
online-softmax state (m, l, acc) lives in VMEM scratch; K/V stream through
in (bk, hd) tiles.  Tiles are 128-aligned for the MXU; GQA is handled in
ops.py by an index_map that maps query heads onto their shared KV head, so
KV tiles are NOT replicated in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import divisor_tile

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, bq: int, bk: int, scale: float, causal: bool,
                  window: int | None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                        # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                        # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)                        # (bk, hd)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    window: int | None = None, bq: int = 128, bk: int = 128):
    """q: (BH, Sq, hd); k/v: (BH, Sk, hd) — heads pre-flattened (GQA mapping
    done by the caller in ops.py).  Returns (BH, Sq, hd) in q.dtype.
    ``interpret`` runs the Pallas interpreter instead of Mosaic."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    # requested tiles are upper bounds (see kernels/tiling.py): model seq
    # lengths need not be 128-aligned
    bq = divisor_tile(Sq, bq)
    bk = divisor_tile(Sk, bk)
    grid = (BH, Sq // bq, Sk // bk)
    scale = 1.0 / (hd ** 0.5)
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
