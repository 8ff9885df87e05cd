"""Jit'd wrapper: model-facing fused cross-entropy.

``fused_xent_sum`` is the surface ``lm_loss_fn`` consumes.  The kernel
lowers to Mosaic unless the caller asks for ``interpret=True`` (the
``--kernels interpret`` correctness harness).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_xent.kernel import fused_xent
from repro.kernels.fused_xent.ref import xent_ref


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_xent_sum(h, w, labels, mask, vocab_size: int, interpret: bool):
    """h: (B,S,d); w: (d,Vp); labels/mask: (B,S) -> (sum_nll, sum_mask).

    Forward runs the Pallas streaming kernel; backward uses the analytic
    softmax gradient (p − onehot) computed in sequence chunks (a bwd kernel
    is the TPU follow-up; the fwd kernel is the ISGD hot path since the
    controller and the Alg.2 early-stop check only need ψ)."""
    return _fwd_value(h, w, labels, mask, vocab_size, interpret)


def _fwd_value(h, w, labels, mask, vocab_size, interpret):
    B, S, d = h.shape
    N = B * S
    nll = fused_xent(h.reshape(N, d), w, labels.reshape(N),
                     vocab_size=vocab_size, interpret=interpret)
    m = mask.reshape(N).astype(jnp.float32)
    return jnp.sum(nll * m), jnp.sum(m)


def _fwd(h, w, labels, mask, vocab_size, interpret):
    out = _fwd_value(h, w, labels, mask, vocab_size, interpret)
    return out, (h, w, labels, mask)


def _bwd(vocab_size, interpret, res, g):
    h, w, labels, mask = res
    g_tot, _ = g
    B, S, d = h.shape
    Vp = w.shape[1]
    c = S
    while c > 512 and c % 2 == 0:
        c //= 2
    n = S // c

    def chunk(i):
        hs = jax.lax.dynamic_slice_in_dim(h, i * c, c, axis=1)
        ys = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask, i * c, c, axis=1)
        logits = (hs.astype(jnp.float32) @ w.astype(jnp.float32))
        if vocab_size != Vp:
            vmask = jnp.arange(Vp) < vocab_size
            logits = jnp.where(vmask, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        delta = p - jax.nn.one_hot(ys, Vp, dtype=jnp.float32)
        delta *= (ms.astype(jnp.float32) * g_tot)[..., None]
        dh = (delta @ w.astype(jnp.float32).T).astype(h.dtype)
        dw = jnp.einsum("bsd,bsv->dv", hs.astype(jnp.float32), delta)
        return dh, dw

    def body(carry, i):
        dw_acc = carry
        dh_c, dw_c = chunk(i)
        return dw_acc + dw_c, dh_c

    dw, dhs = jax.lax.scan(body, jnp.zeros((d, Vp), jnp.float32),
                           jnp.arange(n))
    dh = jnp.moveaxis(dhs, 0, 1).reshape(B, S, d)      # (n,B,c,d) -> (B,S,d)
    return dh, dw.astype(w.dtype), None, None


fused_xent_sum.defvjp(_fwd, _bwd)


def xent_ref_sum(h, w, labels, mask, vocab_size: int):
    B, S, d = h.shape
    N = B * S
    nll = xent_ref(h.reshape(N, d), w, labels.reshape(N), vocab_size=vocab_size)
    m = mask.reshape(N).astype(jnp.float32)
    return jnp.sum(nll * m), jnp.sum(m)
