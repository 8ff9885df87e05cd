"""Model-facing wrapper: GQA layout -> flash kernel.

Maps (B, S, H, hd) q and (B, S, K, hd) k/v onto the kernel's flattened
(B·H, S, hd) layout; the shared KV head of each query-head group is
repeated per query head (``jnp.repeat``, a copy of K and V in HBM).

``gqa_flash`` is trainable: the forward runs the Pallas kernel and saves
``(q, k, v, o, lse)``; the backward is the Pallas kernel
``flash_attention_bwd``, which recomputes the probabilities tile by tile
from the saved log-sum-exp.  The vjp of the K/V repeat sums dK/dV over
each query-head group.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_attention,
                                                  flash_attention_bwd)
from repro.kernels.flash_attention.ref import attention_ref


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, bq, bk, interpret):
    return flash_attention(q, k, v, causal=causal, window=window,
                           bq=bq, bk=bk, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, bq, bk, interpret):
    o, lse = flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                             bk=bk, interpret=interpret, save_lse=True)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, bq, bk, interpret, res, g):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, g, causal=causal,
                               window=window, bq=bq, bk=bk,
                               interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def gqa_flash(q, k, v, *, interpret: bool, causal=True, window=None, bq=None,
              bk=None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd) -> (B, Sq, H, hd).
    ``bq``/``bk`` default to the kernel's choice for the shape.
    ``interpret`` runs the kernel through the Pallas interpreter instead of
    Mosaic."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    rep = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    of = _flash(qf, kf, vf, causal, window, bq, bk, interpret)
    return of.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def gqa_ref(q, k, v, *, causal=True, window=None):
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    rep = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    of = attention_ref(qf, kf, vf, causal=causal, window=window)
    return of.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
