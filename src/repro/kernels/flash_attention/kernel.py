"""Blocked (flash-style) causal attention Pallas kernels, forward and
backward, with optional sliding window.

Forward grid: (batch·heads, q_tiles, k_tiles) with k minor.  Per (bh, q)
tile the online-softmax state (m, l, acc) lives in VMEM scratch; K/V stream
through in (bk, hd) tiles.  The grid visits every (q, k) tile pair, but only
the pairs that hold an unmasked entry do work: ``live_range`` gives query
tile ``i`` its live key tiles, the tile body runs under ``pl.when`` on them,
and the K/V index maps clamp to the same range, so the pipeline keeps the
block it holds instead of fetching a dead one.  For the backward the
forward also writes each row's log-sum-exp ``m + log l`` as a lane-dense
``(BH, 1, Sq)`` f32 row.

Backward grid: (batch·heads, k_tiles, q_tiles) with q minor, one kernel.
Per key tile dK and dV accumulate in VMEM scratch while the live query
tiles (``live_q_range``) stream through; dQ accumulates in an f32
``(Sq, hd)`` scratch held for the head's whole sweep.  A tile recomputes
the scores and probabilities ``p = exp(s − lse)`` in (key, query)
orientation, so that lse and delta broadcast as rows, and does five
matmuls: ``s = k·qᵀ``, ``dp = v·dOᵀ``, ``dV += p·dO``, ``dK += ds·q`` and
``dQ += dsᵀ·k``.  Default tiles are chosen from the shape (``tiling.flash_tiles``,
``tiling.flash_bwd_tiles``).  GQA is handled in ops.py, which repeats K/V
per query head before the call (an HBM copy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import divisor_tile, flash_bwd_tiles, flash_tiles

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


def live_q_range(j, *, bq: int, bk: int, nq: int, causal: bool,
                 window: int | None):
    """First and last query tile (inclusive) holding an unmasked entry for
    key tile ``j``, from the same mask rules as ``live_range``.  Under
    ``causal`` the first is the tile of the key tile's first key; under
    ``window`` the last is the tile of its last key + window − 1."""
    first = jnp.minimum(j * bk // bq, nq - 1) if causal else 0
    last = (nq - 1 if window is None else
            jnp.clip(((j + 1) * bk + window - 2) // bq, 0, nq - 1))
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


def _mask(qpos, kpos, causal: bool, window: int | None):
    mask = jnp.ones(qpos.shape, dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs, bq: int, bk: int,
                  scale: float, causal: bool, window: int | None,
                  save_lse: bool):
    lse_ref = refs[0] if save_lse else None
    m_ref, l_ref, acc_ref = refs[-3:]
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
        s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)

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
        if lse_ref is not None:
            # (bq, 1) column -> lane-dense (1, bq) row, through a transpose
            # of lane-aligned shape
            lse = jnp.broadcast_to(m_ref[...] + jnp.log(safe), (bq, 128))
            lse_ref[0] = jnp.transpose(lse)[:1]


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                      *, bq: int, bk: int, scale: float, causal: bool,
                      window: int | None):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile():
        q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
        v = v_ref[0].astype(jnp.float32)                    # (bk, hd)
        do = do_ref[0].astype(jnp.float32)                  # (bq, hd)
        nt = (((1,), (1,)), ((), ()))
        # transposed scores (bk, bq): lse and delta broadcast as rows
        s = jax.lax.dot_general(k, q, nt,
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                         # (bk, bq)
        dp = jax.lax.dot_general(v, do, nt,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dv_acc[...] += jnp.dot(p, do, preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dq_acc[rows, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    first, last = live_q_range(ki, bq=bq, bk=bk, nq=nq, causal=causal,
                               window=window)
    pl.when((qi >= first) & (qi <= last))(tile)

    @pl.when(qi == nq - 1)
    def _finish_dkv():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((ki == nk - 1) & (qi == nq - 1))
    def _finish_dq():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_vmem_limit(Sq: int, bq: int, bk: int, hd: int, itemsize: int):
    """Scoped VMEM bytes to ask of Mosaic for the backward: None (its
    default, 16 MiB) where the estimate below fits under 14 MiB, else the
    estimate and half as much again.  The estimate: the tile body's four
    f32 (bk, bq) arrays, the dQ scratch and its output block, and the
    double-buffered tile blocks."""
    body = 4 * bq * bk * 4
    dq = Sq * hd * (4 + 2 * itemsize)
    blocks = 2 * (2 * bq + 4 * bk) * hd * itemsize + 2 * bk * hd * 4
    need = body + dq + blocks
    return None if need <= 14 * 2**20 else int(1.5 * need)


def _tiles(Sq, Sk, bq, bk, auto):
    """Requested tiles are upper bounds (see kernels/tiling.py): model seq
    lengths need not be 128-aligned."""
    auto_q, auto_k = auto
    return divisor_tile(Sq, bq or auto_q), divisor_tile(Sk, bk or auto_k)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret", "save_lse"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    window: int | None = None, bq: int | None = None,
                    bk: int | None = None, save_lse: bool = False):
    """q: (BH, Sq, hd); k/v: (BH, Sk, hd) — heads pre-flattened (GQA mapping
    done by the caller in ops.py).  Returns (BH, Sq, hd) in q.dtype, and
    with ``save_lse`` (set by the vjp's forward, ops.py) also each row's
    log-sum-exp as a (BH, 1, Sq) f32 row for ``flash_attention_bwd``.
    ``bq``/``bk`` default to ``tiling.flash_tiles`` of the shape.
    ``interpret`` runs the Pallas interpreter instead of Mosaic."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    bq, bk = _tiles(Sq, Sk, bq, bk, flash_tiles(Sq, Sk, hd))
    nk = Sk // bk
    grid = (BH, Sq // bq, nk)
    scale = 1.0 / (hd ** 0.5)

    def kv_index(b, i, j):
        first, last = live_range(i, bq=bq, bk=bk, nk=nk, causal=causal,
                                 window=window)
        return b, jnp.clip(j, first, last), 0

    out_specs = pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype)
    if save_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window, save_lse=save_lse),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, interpret: bool,
                        causal: bool = True, window: int | None = None,
                        bq: int | None = None, bk: int | None = None):
    """The vjp of ``flash_attention``: (dq, dk, dv) in the dtypes of q, k,
    v, from the forward's inputs, its output ``o``, its log-sum-exp row
    ``lse`` (``save_lse``) and the cotangent ``do``.  ``bq``/``bk``
    default to ``tiling.flash_bwd_tiles`` of the shape."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    bq, bk = _tiles(Sq, Sk, bq, bk, flash_bwd_tiles(Sq, Sk, hd))
    nq = Sq // bq
    grid = (BH, Sk // bk, nq)
    scale = 1.0 / (hd ** 0.5)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]                   # (BH, 1, Sq)

    def live_q(j, i):
        first, last = live_q_range(j, bq=bq, bk=bk, nq=nq, causal=causal,
                                   window=window)
        return jnp.clip(i, first, last)

    q_spec = pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, live_q(j, i), 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, live_q(j, i)))
    kv_spec = pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((1, Sq, hd), lambda b, j, i: (b, 0, 0)),
                   kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((Sq, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_limit(Sq, bq, bk, hd,
                                             q.dtype.itemsize)),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
