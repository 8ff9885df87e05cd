"""Mamba2 SSD intra-chunk Pallas kernel.

Per grid cell (batch·chunk, head) the kernel computes, entirely in VMEM:
  * the decay matrix L[i,j] = exp(cum_i − cum_j) (i ≥ j), from the chunk's
    cumulative ``dt·A`` (``cum``, precomputed by the caller),
  * the diagonal-block output Y_diag = ((C·Bᵀ) ⊙ L) · (x·dt),
  * the chunk's boundary state  S = Σ_j exp(cum_last − cum_j)·(x·dt)_j ⊗ B_j.
The chunk decay exp(cum_last) and the O(S/chunk)-step inter-chunk recurrence
run in ops.py (they are tiny: (nh,) and (nh, hd, ds) per step).

Layout is head-major so the head axis never lands in a block's last two
dims, which Mosaic tiles by (8, 128) unless they span the whole array:
x (cl, hd), B/C (cl, ds), y (cl, hd), state (hd, ds) per cell; ``dt`` and
``cum`` come in as a (cl, 1) column and ``cum`` also as a (1, cl) row, so
L is an outer difference with no in-kernel transpose.  B/C stay per group:
the index_map sends head h to group h // (nh // G).  With cl=chunk≤256,
hd=64, ds=128 the three matmuls hit the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref,
                y_ref, state_ref, *, cl: int):
    x = x_ref[0, 0].astype(jnp.float32)         # (cl, hd)
    dt = dt_ref[0, 0]                           # (cl, 1)
    cum_c = cumc_ref[0, 0]                      # (cl, 1)
    cum_r = cumr_ref[0, 0]                      # (1, cl)
    B = b_ref[0, 0].astype(jnp.float32)         # (cl, ds)
    C = c_ref[0, 0].astype(jnp.float32)         # (cl, ds)

    tri = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    L = jnp.exp(jnp.where(tri, cum_c - cum_r, -1e30))

    xdt = x * dt                                # (cl, hd)
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (cl, cl)
    y = jax.lax.dot_general(CB * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (cl, hd)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # cum_last as a masked lane reduction: Mosaic cannot broadcast a
    # (1, 1) slice taken at lane cl-1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, cl), 1)
    last = jnp.sum(jnp.where(lane == cl - 1, cum_r, 0.0), axis=1,
                   keepdims=True)               # (1, 1)
    w = jnp.exp(last - cum_c)                   # (cl, 1)
    state = jax.lax.dot_general(xdt * w, B, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (hd, ds)
    state_ref[0, 0] = state


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(x, dt, cum, B, C, *, interpret: bool):
    """x: (N, nh, cl, hd); dt, cum: (N, nh, cl) f32; B/C: (N, G, cl, ds).
    N = batch·n_chunks; ``cum`` is the within-chunk cumulative sum of
    ``dt·A``.  ``interpret`` runs the Pallas interpreter instead of Mosaic.

    Returns (y_diag (N, nh, cl, hd) f32, states (N, nh, hd, ds) f32)."""
    N, nh, cl, hd = x.shape
    G, ds = B.shape[1], B.shape[-1]
    rep = nh // G
    col = pl.BlockSpec((1, 1, cl, 1), lambda n, h: (n, h, 0, 0))
    grp = pl.BlockSpec((1, 1, cl, ds), lambda n, h: (n, h // rep, 0, 0))
    return pl.pallas_call(
        functools.partial(_ssd_kernel, cl=cl),
        grid=(N, nh),
        in_specs=[
            pl.BlockSpec((1, 1, cl, hd), lambda n, h: (n, h, 0, 0)),
            col,
            col,
            pl.BlockSpec((1, 1, 1, cl), lambda n, h: (n, h, 0, 0)),
            grp,
            grp,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cl, hd), lambda n, h: (n, h, 0, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda n, h: (n, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, nh, cl, hd), jnp.float32),
            jax.ShapeDtypeStruct((N, nh, hd, ds), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt[..., None], cum[..., None], cum[:, :, None, :], B, C)
