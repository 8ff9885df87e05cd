"""Blocked (flash-style) causal attention Pallas kernel with optional
sliding window.

Grid: (batch·heads, q_tiles, k_tiles) with k minor.  Per (bh, q) tile the
online-softmax state (m, l, acc) lives in VMEM scratch; K/V stream through
in (bk, hd) tiles.  The grid visits every (q, k) tile pair, but only the
pairs that hold an unmasked entry do work: ``live_range`` gives query tile
``i`` its live key tiles, the tile body runs under ``pl.when`` on them, and
the K/V index maps clamp to the same range, so the pipeline keeps the block
it holds instead of fetching a dead one.  Default tiles are chosen from the
shape (``tiling.flash_tiles``).  GQA is handled in ops.py, which
repeats K/V per query head before the call (an HBM copy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import divisor_tile, flash_tiles

NEG_INF = -1e30


def live_range(i, *, bq: int, bk: int, nk: int, causal: bool,
               window: int | None):
    """First and last key tile (inclusive) holding an unmasked entry for
    query tile ``i``; ``i`` may be a Python int or a traced index.  Under
    ``causal`` the last is the tile of the tile's last query; under
    ``window`` the first is the tile of the first query's oldest key."""
    first = 0 if window is None else jnp.clip((i * bq - window + 1) // bk,
                                              0, nk - 1)
    last = jnp.minimum(((i + 1) * bq - 1) // bk, nk - 1) if causal else nk - 1
    return first, last


def live_tiles(Sq: int, Sk: int, bq: int, bk: int, causal: bool,
               window: int | None) -> int:
    """(q, k) tile pairs per head that the kernel does work on."""
    nk = Sk // bk
    total = 0
    for i in range(Sq // bq):
        first, last = live_range(i, bq=bq, bk=bk, nk=nk, causal=causal,
                                 window=window)
        total += int(last) - int(first) + 1
    return total


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

    def tile():
        q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
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

        m_prev = m_ref[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)                    # (bk, hd)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    first, last = live_range(qi, bq=bq, bk=bk, nk=nk, causal=causal,
                             window=window)
    pl.when((ki >= first) & (ki <= last))(tile)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    window: int | None = None, bq: int | None = None,
                    bk: int | None = None):
    """q: (BH, Sq, hd); k/v: (BH, Sk, hd) — heads pre-flattened (GQA mapping
    done by the caller in ops.py).  Returns (BH, Sq, hd) in q.dtype.
    ``bq``/``bk`` default to ``tiling.flash_tiles`` of the shape.
    ``interpret`` runs the Pallas interpreter instead of Mosaic."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    auto_q, auto_k = flash_tiles(Sq, Sk, hd)
    # requested tiles are upper bounds (see kernels/tiling.py): model seq
    # lengths need not be 128-aligned
    bq = divisor_tile(Sq, bq or auto_q)
    bk = divisor_tile(Sk, bk or auto_k)
    nk = Sk // bk
    grid = (BH, Sq // bq, nk)
    scale = 1.0 / (hd ** 0.5)

    def kv_index(b, i, j):
        first, last = live_range(i, bq=bq, bk=bk, nk=nk, causal=causal,
                                 window=window)
        return b, jnp.clip(j, first, last), 0

    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
