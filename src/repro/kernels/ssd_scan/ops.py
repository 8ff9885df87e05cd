"""Jit'd wrapper: full SSD forward = Pallas intra-chunk kernel + lax.scan
inter-chunk recurrence + off-diagonal contribution.

``ssd_chunked_pallas`` is trainable: the forward runs the Pallas kernel,
the backward differentiates the block-matmul reference (``models.ssm.
ssd_chunked`` — the same chunk decomposition, so the recompute cost matches
a flash-style backward; a fused bwd kernel is the TPU follow-up)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_intra_chunk


def _ssd_pallas_fwd(x, dt, A, B, C, chunk: int, interpret: bool):
    """Same contract as models.ssm.ssd_chunked.

    x: (b, S, nh, hd); dt: (b, S, nh); A: (nh,); B/C: (b, S, G, ds).
    -> (y (b, S, nh, hd) f32, final_state (b, nh, hd, ds) f32)
    """
    b, S, nh, hd = x.shape
    G, ds = B.shape[-2], B.shape[-1]
    cl = min(chunk, S)
    while S % cl:                 # largest dividing chunk <= requested
        cl -= 1
    nc = S // cl
    rep = nh // G

    # head-/group-major chunks for the kernel (see kernel.py)
    def chunks(a):                # (b, S, h, ...) -> (b·nc, h, cl, ...)
        return jnp.moveaxis(a.reshape((b * nc, cl) + a.shape[2:]), 1, 2)

    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum(chunks(dt * A), axis=-1)              # (b·nc, nh, cl)
    y_diag, states = ssd_intra_chunk(chunks(x), chunks(dt), cum, chunks(B),
                                     chunks(C), interpret=interpret)
    y_diag = jnp.moveaxis(y_diag, 1, 2).reshape(b, nc, cl, nh, hd)
    states = states.reshape(b, nc, nh, hd, ds)
    decays = jnp.exp(cum[..., -1]).reshape(b, nc, nh)

    def step(state, inp):
        s_n, d_n = inp
        new = state * d_n[..., None, None] + s_n
        return new, state

    final_state, prevs = jax.lax.scan(
        step, jnp.zeros((b, nh, hd, ds), jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decays, 1, 0)))
    prevs = jnp.moveaxis(prevs, 0, 1)                      # (b, nc, nh, hd, ds)

    # off-diagonal: Y_off[i] = C_i · prev_state · exp(cum_i)
    Ch = jnp.repeat(C, rep, axis=-2).reshape(b, nc, cl, nh, ds)
    Y_off = jnp.einsum("bnihd,bnhpd,bnhi->bnihp", Ch.astype(jnp.float32),
                       prevs, jnp.exp(cum).reshape(b, nc, nh, cl))
    y = (y_diag + Y_off).reshape(b, S, nh, hd)
    return y, final_state


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, B, C, chunk, interpret):
    return _ssd_pallas_fwd(x, dt, A, B, C, chunk, interpret)


def _ssd_fwd(x, dt, A, B, C, chunk, interpret):
    return _ssd(x, dt, A, B, C, chunk, interpret), (x, dt, A, B, C)


def _ssd_bwd(chunk, interpret, res, g):
    x, dt, A, B, C = res
    from repro.models.ssm import ssd_chunked   # lazy: models lazily import us
    _, vjp = jax.vjp(
        lambda x_, dt_, A_, B_, C_: ssd_chunked(x_, dt_, A_, B_, C_,
                                                chunk=chunk),
        x, dt, A, B, C)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_chunked_pallas(x, dt, A, B, C, *, chunk: int, interpret: bool):
    """Trainable surface — see module docstring; contract of
    ``_ssd_pallas_fwd``.  ``interpret`` runs the kernel through the Pallas
    interpreter instead of Mosaic."""
    return _ssd(x, dt, A, B, C, chunk, interpret)
