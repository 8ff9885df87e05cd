"""InternLM2 (arXiv:2403.17297), plain float32: a decoder of pre-norm blocks,
RMSNorm -> GQA attention with rotary positions (rotate-half, theta from the
config) -> residual, RMSNorm -> SwiGLU MLP -> residual; final RMSNorm and an
untied LM head.  Query head h reads key/value head h // (heads / kv_heads).

Departures: the norm gain is stored as (1 + scale); the weights are drawn
here from the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import (F32, fan_in_normal, leaf_name, mm,
                                    next_token_loss, rms_norm)

Q_BLOCK = 512


def init(key, shapes, model):
    """Weights for the program's parameter tree ``shapes``: matrices normal
    with std 1/sqrt(fan_in), the embedding with std 1/sqrt(d_model), norm
    scales 0 (gain 1)."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, sds) in zip(keys, flat):
        name = leaf_name(path)
        stacked = any(getattr(p, "key", None) == "blocks" for p in path)
        if name in ("ln1", "ln2", "final_norm"):
            out.append(jnp.zeros(sds.shape, sds.dtype))
        elif name == "embed":
            out.append((jax.random.normal(k, sds.shape, F32)
                        / math.sqrt(sds.shape[1])).astype(sds.dtype))
        else:
            out.append(fan_in_normal(k, sds, 1 if stacked else 0))
    return jax.tree_util.tree_unflatten(tree, out)


def rope(x, theta):
    """x: (B, S, H, hd); rotate-half convention."""
    B, S, H, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Causal softmax attention in blocks of query rows.
    q: (B, S, H, hd); k, v: (B, S, K, hd)."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    rep = H // K
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(Q_BLOCK, S)
    while S % qb:
        qb -= 1
    scale = 1.0 / math.sqrt(hd)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i, qi):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    qr = jnp.moveaxis(q.reshape(B, S // qb, qb, H, hd), 1, 0)
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(S // qb), qr))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def layer(x, p, model, q):
    B, S, d = x.shape
    H, K, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    a = p["mixer"]
    h = rms_norm(x, p["ln1"], eps)
    qh = rope(mm(q, h, a["wq"]).reshape(B, S, H, hd), model["rope_theta"])
    kh = rope(mm(q, h, a["wk"]).reshape(B, S, K, hd), model["rope_theta"])
    vh = mm(q, h, a["wv"]).reshape(B, S, K, hd)
    o = attention(q(qh), q(kh), q(vh)).reshape(B, S, H * hd)
    x = x + mm(q, o, a["wo"])
    m = p["mlp"]
    h = rms_norm(x, p["ln2"], eps)
    g = jax.nn.silu(mm(q, h, m["wg"])) * mm(q, h, m["wi"])
    return x + mm(q, g, m["wo"])


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
