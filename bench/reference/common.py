"""Pieces the plain references share: weight draws, norms, the matmul hook,
and the next-token cross-entropy in row blocks."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def ident(x):
    return x


def _scaled_round(x, dtype, largest):
    """Round ``x`` to ``dtype`` with one scale per tensor,
    ``s = max|x| / largest``."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    """The control's precision, as fp8 training runs: each matmul operand
    rounded to float8_e4m3fn, and its gradient to float8_e5m2, each with
    one scale per tensor."""
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, 57344.0),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(q, a, b):
    """``a @ b`` in float32 at the highest precision, operands through ``q``."""
    return jnp.matmul(q(a), q(b), precision=jax.lax.Precision.HIGHEST)


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def fan_in_normal(key, sds, lead: int):
    """std 1/sqrt(fan_in), fan_in = the matrix's input width (the axis after
    ``lead`` stacking axes)."""
    fan_in = sds.shape[lead]
    return normal(key, sds.shape, 1.0 / math.sqrt(fan_in), sds.dtype)


def rms_norm(x, gain_minus_one, eps):
    x = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * (1.0 + gain_minus_one.astype(F32))


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def next_token_loss(h, head, tokens, vocab: int, q, rows: int = 1024):
    """Mean next-token cross-entropy over positions 0..S-2 of every row.
    h: (B, S, d) final hidden states; head: (d, Vp).  Logits of the padded
    vocabulary columns (>= vocab) are left out of the softmax.  Computed in
    blocks of ``rows`` positions so that the f32 logits fit."""
    B, S, d = h.shape
    labels = jnp.roll(tokens, -1, axis=1).reshape(-1)
    valid = jnp.ones((B, S), F32).at[:, -1].set(0.0).reshape(-1)
    hf = h.reshape(B * S, d)
    n = B * S
    rows = min(rows, n)
    while n % rows:
        rows -= 1
    Vp = head.shape[1]
    col_ok = jnp.arange(Vp) < vocab

    @jax.checkpoint
    def block(hb, yb, mb):
        logits = mm(q, hb, head)
        logits = jnp.where(col_ok, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - gold) * mb)

    def body(tot, xs):
        return tot + block(*xs), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), F32),
                          (hf.reshape(n // rows, rows, d),
                           labels.reshape(n // rows, rows),
                           valid.reshape(n // rows, rows)))
    return tot / jnp.sum(valid)
