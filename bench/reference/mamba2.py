"""Mamba2 (arXiv:2405.21060), plain float32: a stack of pre-norm Mamba2
mixers.  Per layer: RMSNorm; in_proj to [z, xBC, dt]; depthwise causal
conv (width d_conv, with bias) and SiLU on xBC, split into x, B, C;
dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD scan (the paper's
minimal discrete form, chunked); y + D x; RMSNorm(y * SiLU(z)); out_proj;
residual.  Final RMSNorm and an LM head.

Departures: the LM head is not tied to the embedding (as in the repo's model
file); the norm gain is stored as (1 + scale); the weights are drawn here
from the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import (F32, fan_in_normal, leaf_name, mm,
                                    next_token_loss, rms_norm)


def init(key, shapes, model):
    """Matrices normal with std 1/sqrt(fan_in) (the conv: fan_in = d_conv),
    the embedding with std 1/sqrt(d_model), A = -U(1, 16), dt_bias the
    inverse softplus of a log-uniform dt in [1e-3, 1e-1], D = 1, norm
    scales and the conv bias 0 — the published Mamba2 initialisation."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, sds) in zip(keys, flat):
        name = leaf_name(path)
        stacked = any(getattr(p, "key", None) == "blocks" for p in path)
        shape, dt = sds.shape, sds.dtype
        if name in ("ln1", "final_norm", "gnorm", "conv_b"):
            out.append(jnp.zeros(shape, dt))
        elif name == "D":
            out.append(jnp.ones(shape, dt))
        elif name == "A_log":
            out.append(jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
                       .astype(dt))
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, F32, math.log(1e-3),
                                              math.log(1e-1)))
            out.append((step + jnp.log(-jnp.expm1(-step))).astype(dt))
        elif name == "embed":
            out.append((jax.random.normal(k, shape, F32)
                        / math.sqrt(shape[1])).astype(dt))
        else:
            out.append(fan_in_normal(k, sds, 1 if stacked else 0))
    return jax.tree_util.tree_unflatten(tree, out)


def segsum(a):
    """a: (..., T) -> (..., T, T) with [i, j] = sum a[j+1..i] for i >= j,
    -inf above the diagonal."""
    T = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    s = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd(x, a, b, c, chunk, q):
    """One sequence, heads in G groups of r.  x: (S, G, r, p) (already
    times dt); a: (S, G, r) (dt * A); b, c: (S, G, n).  -> (S, G, r, p)."""
    S, G, r, p = x.shape
    n = b.shape[-1]
    nc = S // chunk
    x = q(x).reshape(nc, chunk, G, r, p)
    b = q(b).reshape(nc, chunk, G, n)
    c = q(c).reshape(nc, chunk, G, n)
    a = jnp.moveaxis(a.reshape(nc, chunk, G, r), (2, 3), (0, 1))  # (G,r,nc,l)
    a_cum = jnp.cumsum(a, axis=-1)
    hi = jax.lax.Precision.HIGHEST
    L = jnp.exp(segsum(a))                                     # (G,r,nc,l,l)
    cb = jnp.einsum("clgn,csgn->gcls", c, b, precision=hi)
    y_diag = jnp.einsum("gcls,grcls,csgrp->clgrp", cb, L, x, precision=hi)
    decay = jnp.exp(a_cum[..., -1:] - a_cum)                   # (G,r,nc,l)
    states = jnp.einsum("clgn,grcl,clgrp->cgrpn", b, decay, x, precision=hi)
    states = jnp.concatenate([jnp.zeros((1, G, r, p, n), F32), states], 0)
    chunk_decay = jnp.exp(segsum(jnp.pad(a_cum[..., -1],
                                         ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("grzc,cgrpn->zgrpn", chunk_decay, states,
                        precision=hi)[:-1]
    y_off = jnp.einsum("clgn,cgrpn,grcl->clgrp", c, states, jnp.exp(a_cum),
                       precision=hi)
    return (y_diag + y_off).reshape(S, G, r, p)


def layer(x, p, model, q):
    B, S, d = x.shape
    di = model["ssm_expand"] * d
    hd, n, G = model["ssm_headdim"], model["ssm_state"], model["ssm_ngroups"]
    nh = di // hd
    r = nh // G
    cw = model["conv_width"]
    m = p["mixer"]
    u = rms_norm(x, p["ln1"], model["norm_eps"])
    zxbcdt = mm(q, u, m["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * G * n],
                  zxbcdt[..., 2 * di + 2 * G * n:])
    w = m["conv_w"].astype(F32)                                # (cw, C)
    pad = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + S] * w[i] for i in range(cw)) + m["conv_b"].astype(F32)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(B, S, G, r, hd)
    bm = xbc[..., di:di + G * n].reshape(B, S, G, n)
    cm = xbc[..., di + G * n:].reshape(B, S, G, n)
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32)).reshape(B, S, G, r)
    A = -jnp.exp(m["A_log"].astype(F32)).reshape(G, r)
    chunk = model["ssm_chunk"]

    @jax.checkpoint
    def row(xs_, dt_, b_, c_):
        return ssd(xs_ * dt_[..., None], dt_ * A, b_, c_, chunk, q)

    y = jax.lax.map(lambda t: row(*t), (xs, dt, bm, cm))
    y = y + m["D"].astype(F32).reshape(G, r)[:, :, None] * xs
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = rms_norm(y, m["gnorm"], model["norm_eps"])
    return x + mm(q, y, m["out_proj"])


def loss(params, tokens, model, q):
    """Mean next-token cross-entropy of ``tokens`` (B, S) in float32."""
    x = jnp.take(params["embed"].astype(F32), tokens, axis=0)
    (blocks,) = params["blocks"]

    @jax.checkpoint
    def body(x, p):
        return layer(x, p, model, q), None

    x, _ = jax.lax.scan(body, x, blocks)
    x = rms_norm(x, params["final_norm"], model["norm_eps"])
    return next_token_loss(x, params["head"], tokens, model["vocab_size"], q)
