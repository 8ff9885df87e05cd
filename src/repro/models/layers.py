"""Core transformer layers: RMSNorm, RoPE, SwiGLU MLP, GQA attention with
optional sliding window, and DeepSeek-V2 MLA (multi-head latent attention).

All functions are pure (params passed explicitly) and shard-friendly: the
attention reference path chunks queries with ``lax.scan`` so the materialized
score block is (B, H, q_chunk, S) rather than (B, H, S, S) — the same tiling
the Pallas flash kernel uses, which keeps the dry-run memory profile honest.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.analysis.mode import scan_unroll

DEFAULT_Q_CHUNK = 512


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype=jnp.bfloat16):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                                  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv      # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]                          # (..., seq, 1, hd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(key, d_model, d_ff, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": dense_init(k1, (d_model, d_ff), dtype),
        "wi": dense_init(k2, (d_model, d_ff), dtype),
        "wo": dense_init(k3, (d_ff, d_model), dtype),
    }


def mlp(params, x, activation="silu"):
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    h = act(x @ params["wg"]) * (x @ params["wi"])
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# chunked-attention core (shared by self/cross, train/prefill)
# ---------------------------------------------------------------------------
def _attend_chunked(q, k, v, *, causal: bool, window: Optional[int],
                    q_offset=0, q_chunk: int = DEFAULT_Q_CHUNK):
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd) with H = K*rep.

    Scans over query chunks; materializes (B, H, qc, Sk) scores per chunk.
    ``q_offset`` is the absolute position of q[0] relative to k[0].
    """
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    rep = H // K
    qc = min(q_chunk, Sq)
    while Sq % qc:                   # largest divisor of Sq <= q_chunk
        qc -= 1
    n_chunks = Sq // qc

    qr = q.reshape(B, n_chunks, qc, K, rep, hd).transpose(1, 0, 2, 3, 4, 5)
    scale = 1.0 / math.sqrt(hd)
    kpos = jnp.arange(Sk)

    def chunk(carry, inputs):
        ci, qb = inputs                                       # qb: (B, qc, K, rep, hd)
        s = jnp.einsum("bqkrd,bskd->bkrqs", qb, k).astype(jnp.float32) * scale
        qpos = q_offset + ci * qc + jnp.arange(qc)            # (qc,)
        mask = jnp.ones((qc, Sk), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkrqs,bskd->bqkrd", p, v)
        return carry, o

    _, out = jax.lax.scan(chunk, None, (jnp.arange(n_chunks), qr),
                          unroll=scan_unroll())
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, v.shape[-1])
    return out


def decode_attend(q, k_cache, v_cache, t, *, window: Optional[int]):
    """Single-token attention against a cache.

    q: (B, 1, H, hd); caches: (B, S, K, hd); t: index of the new token —
    a scalar, or a (B,) vector of per-row cursors (continuous-batching
    slots, where every row of the batch sits at its own position).
    """
    B, _, H, hd = q.shape
    _, S, K, _ = k_cache.shape
    dv = v_cache.shape[-1]               # MLA: value dim != query dim
    rep = H // K
    qr = q.reshape(B, K, rep, hd)
    s = jnp.einsum("bkrd,bskd->bkrs", qr, k_cache).astype(jnp.float32)
    s *= 1.0 / math.sqrt(hd)
    kpos = jnp.arange(S)
    tb = jnp.asarray(t, jnp.int32).reshape(-1)[:, None]      # (B,1) or (1,1)
    mask = kpos[None, :] <= tb
    if window is not None:
        mask &= kpos[None, :] > tb - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkrs,bskd->bkrd", p, v_cache)
    return o.reshape(B, 1, H, dv)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------
def init_attn(key, cfg, dtype=jnp.bfloat16):
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, K * hd), dtype),
        "wv": dense_init(ks[2], (d, K * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype),
    }


def attn_forward(params, cfg, x, positions, *, window, use_rope=True,
                 q_chunk=DEFAULT_Q_CHUNK, kernels="reference"):
    """Full-sequence causal attention. x: (B, S, d).

    ``kernels`` ``pallas``/``interpret`` swaps the chunked-scan reference
    path for the Pallas flash kernel (same GQA layout; numerically equal
    within the ``repro.kernels.numerics`` tolerances, bit-identical in
    neither direction — the switch is per-``build_model``, never per-step).
    """
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, H, hd)
    k = (x @ params["wk"]).reshape(B, S, K, hd)
    v = (x @ params["wv"]).reshape(B, S, K, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if kernels != "reference":
        from repro.kernels.flash_attention.ops import gqa_flash
        o = gqa_flash(q, k, v, causal=True, window=window,
                      interpret=kernels == "interpret")
    else:
        o = _attend_chunked(q, k, v, causal=True, window=window,
                            q_chunk=q_chunk)
    return o.reshape(B, S, H * hd) @ params["wo"], (k, v)


def attn_decode(params, cfg, x, cache_k, cache_v, t, *, window, use_rope=True):
    """One-token decode. x: (B, 1, d); caches (B, S, K, hd); returns (out, k, v).

    ``t`` may be a scalar (all rows at the same position — the one-shot
    engine) or a (B,) vector of per-row cursors (slot-based continuous
    batching): the vector path scatters each row's k/v at its own cursor
    and masks attention per row.
    """
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    per_slot = jnp.ndim(t) == 1
    q = (x @ params["wq"]).reshape(B, 1, H, hd)
    k = (x @ params["wk"]).reshape(B, 1, K, hd)
    v = (x @ params["wv"]).reshape(B, 1, K, hd)
    if use_rope:
        pos = jnp.asarray(t)[:, None] if per_slot else jnp.full((1, 1), t)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if per_slot:
        rows = jnp.arange(B)
        cache_k = cache_k.at[rows, t].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, t].set(v[:, 0].astype(cache_v.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, t, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, t, 0, 0))
    o = decode_attend(q, cache_k, cache_v, t, window=window)
    return o.reshape(B, 1, H * hd) @ params["wo"], cache_k, cache_v


def cross_attn_forward(params, cfg, x, enc_kv, q_chunk=DEFAULT_Q_CHUNK):
    """Cross attention (whisper decoder): keys/values from encoder output."""
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Se = enc_kv.shape[1]
    q = (x @ params["wq"]).reshape(B, S, H, hd)
    k = (enc_kv @ params["wk"]).reshape(B, Se, K, hd)
    v = (enc_kv @ params["wv"]).reshape(B, Se, K, hd)
    o = _attend_chunked(q, k, v, causal=False, window=None, q_chunk=q_chunk)
    return o.reshape(B, S, H * hd) @ params["wo"]


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------
def init_mla(key, cfg, dtype=jnp.bfloat16):
    d, H = cfg.d_model, cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, H * (dn + dr)), dtype),
        "wkv_a": dense_init(ks[1], (d, r + dr), dtype),
        "wk_b": dense_init(ks[2], (r, H * dn), dtype),
        "wv_b": dense_init(ks[3], (r, H * dv), dtype),
        "wo": dense_init(ks[4], (H * dv, d), dtype),
        "kv_norm": jnp.zeros((r,), jnp.float32),
    }


def _mla_qkv(params, cfg, x, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv = x @ params["wkv_a"]                                   # (B, S, r + dr)
    c_kv = rms_norm(kv[..., :r], params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)  # (B,S,1,dr)
    return q_nope, q_rope, c_kv, k_rope


def _mla_attend(params, cfg, q_nope, q_rope, c_kv, k_rope, *, causal, q_offset=0):
    """Expands the latent cache and runs chunked attention.

    q_*: (B, Sq, H, *); c_kv: (B, Sk, r); k_rope: (B, Sk, 1, dr).
    """
    B, Sq, H, dn = q_nope.shape
    dv = cfg.v_head_dim
    k_nope = (c_kv @ params["wk_b"]).reshape(B, -1, H, dn)
    v = (c_kv @ params["wv_b"]).reshape(B, -1, H, dv)
    # fold rope part in by concatenation (k_rope broadcast over heads)
    k_rope_b = jnp.broadcast_to(k_rope, (B, k_nope.shape[1], H, k_rope.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    o = _attend_chunked(q, k, v, causal=causal, window=None, q_offset=q_offset)
    return o.reshape(B, Sq, H * dv) @ params["wo"]


def mla_forward(params, cfg, x, positions):
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, positions)
    out = _mla_attend(params, cfg, q_nope, q_rope, c_kv, k_rope, causal=True)
    return out, (c_kv, k_rope.squeeze(2))


def _mla_attend_decode(params, cfg, q_nope, q_rope, c_kv, k_rope_cache, t):
    """Single-token MLA attention with per-row cursors ``t`` (B,).

    Expands the latent cache like :func:`_mla_attend` but runs the masked
    one-token attend (``decode_attend`` with K == H), which supports a
    vector ``t`` — the chunked path's scalar ``q_offset`` cannot.
    """
    B, _, H, dn = q_nope.shape
    dv = cfg.v_head_dim
    k_nope = (c_kv @ params["wk_b"]).reshape(B, -1, H, dn)
    v = (c_kv @ params["wv_b"]).reshape(B, -1, H, dv)
    k_rope_b = jnp.broadcast_to(k_rope_cache[:, :, None, :],
                                (B, k_nope.shape[1], H, k_rope_cache.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    o = decode_attend(q, k, v, t, window=None)
    return o.reshape(B, 1, H * dv) @ params["wo"]


def mla_decode(params, cfg, x, cache_ckv, cache_krope, t):
    """cache_ckv: (B, S, r); cache_krope: (B, S, dr) — the compressed MLA cache.

    ``t`` scalar or (B,) per-row cursors (see :func:`attn_decode`).
    """
    B = x.shape[0]
    per_slot = jnp.ndim(t) == 1
    pos = jnp.asarray(t)[:, None] if per_slot else jnp.full((1, 1), t)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, pos)
    if per_slot:
        rows = jnp.arange(B)
        cache_ckv = cache_ckv.at[rows, t].set(c_kv[:, 0].astype(cache_ckv.dtype))
        cache_krope = cache_krope.at[rows, t].set(
            k_rope[:, 0, 0].astype(cache_krope.dtype))
        out = _mla_attend_decode(params, cfg, q_nope, q_rope, cache_ckv,
                                 cache_krope, t)
        return out, cache_ckv, cache_krope
    cache_ckv = jax.lax.dynamic_update_slice(cache_ckv, c_kv.astype(cache_ckv.dtype), (0, t, 0))
    cache_krope = jax.lax.dynamic_update_slice(
        cache_krope, k_rope.squeeze(2).astype(cache_krope.dtype), (0, t, 0))
    # mask future positions by zeroing their value contribution via score mask:
    # reuse chunked attend with q_offset=t over the full cache, masking via causal
    out = _mla_attend(params, cfg, q_nope, q_rope, cache_ckv,
                      cache_krope[:, :, None, :], causal=True, q_offset=t)
    return out, cache_ckv, cache_krope
