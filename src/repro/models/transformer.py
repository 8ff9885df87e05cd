"""Decoder(-only / hybrid / enc-dec) stack builder.

The layer pattern of every assigned architecture is periodic (DESIGN.md §5):
``block_size()`` layers form one block, and the stack is a ``lax.scan`` over
``n_blocks`` stacked parameter trees — HLO size stays O(block) regardless of
depth, which keeps 512-device dry-run compiles tractable.

Supported per-position specs: mixer ∈ {attn, mla, ssm}, window ∈ {None, int},
mlp ∈ {swiglu, gelu2, moe}, plus a cross-attention slot for enc-dec decoders.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.analysis.mode import scan_unroll
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.obs.timing import ATTN, LM_HEAD, MLP, SSM, named_scope
from repro.sharding import constrain

LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# layer specs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerSpec:
    mixer: str                   # 'attn' | 'mla' | 'ssm'
    window: Optional[int]
    mlp: str                     # 'swiglu' | 'gelu2' | 'moe'
    cross: bool = False


def _mixer_for(cfg, i: int) -> tuple[str, Optional[int]]:
    if cfg.family == "ssm":
        return "ssm", None
    if cfg.family == "hybrid" and cfg.attn_every and not cfg._is_attn_layer(i):
        return "ssm", None
    if cfg.mla:
        return "mla", None
    window = cfg.sliding_window
    if cfg.global_every and (i % cfg.global_every == cfg.global_every - 1):
        window = None                                   # global layer
    return "attn", window


def _mlp_for(cfg, i: int) -> str:
    if cfg._is_moe_layer(i):
        return "moe"
    if cfg.d_ff == 0:
        return "none"                                   # mamba2: mixer-only layers
    return "gelu2" if cfg.family == "encdec" else "swiglu"


def layer_spec(cfg, i: int) -> LayerSpec:
    mixer, window = _mixer_for(cfg, i)
    return LayerSpec(mixer, window, _mlp_for(cfg, i), cross=(cfg.family == "encdec"))


def stack_plan(cfg):
    """-> (prefix_specs, block_specs, n_blocks)."""
    prefix = [layer_spec(cfg, i) for i in range(cfg.first_dense)]
    P = cfg.block_size()
    rest = cfg.num_layers - cfg.first_dense
    assert rest % P == 0, (cfg.name, rest, P)
    n_blocks = rest // P
    block = [layer_spec(cfg, cfg.first_dense + p) for p in range(P)]
    # the pattern must repeat exactly for scan correctness
    for b in range(1, n_blocks):
        for p in range(P):
            assert layer_spec(cfg, cfg.first_dense + b * P + p) == block[p], \
                (cfg.name, b, p)
    return prefix, block, n_blocks


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_layer(key, cfg, spec: LayerSpec, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
         "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}
    if spec.mixer == "attn":
        p["mixer"] = L.init_attn(ks[0], cfg, dtype)
    elif spec.mixer == "mla":
        p["mixer"] = L.init_mla(ks[0], cfg, dtype)
    else:
        p["mixer"] = S.init_ssm(ks[0], cfg, dtype)
    if spec.mlp == "none":
        p.pop("ln2")
        p["mlp"] = {}
    elif spec.mlp == "moe":
        p["mlp"] = M.init_moe(ks[1], cfg, dtype)
    elif spec.mlp == "gelu2":
        p["mlp"] = {"wi": L.dense_init(ks[1], (cfg.d_model, cfg.d_ff), dtype),
                    "wo": L.dense_init(ks[2], (cfg.d_ff, cfg.d_model), dtype)}
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    if spec.cross:
        p["ln_x"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["cross"] = L.init_attn(ks[3], cfg, dtype)
    return p


def init_params(key, cfg, max_seq: int = 0, dtype=jnp.bfloat16):
    prefix, block, n_blocks = stack_plan(cfg)
    keys = jax.random.split(key, 8)
    Vp, d = cfg.padded_vocab, cfg.d_model
    params = {
        "embed": L.dense_init(keys[0], (Vp, d), dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(keys[1], (d, Vp), dtype)
    params["prefix"] = [
        _init_layer(k, cfg, sp, dtype)
        for k, sp in zip(jax.random.split(keys[2], max(1, len(prefix))), prefix)
    ]
    bkeys = jax.random.split(keys[3], n_blocks)
    params["blocks"] = tuple(
        jax.vmap(lambda k: _init_layer(k, cfg, sp, dtype))(
            jax.vmap(lambda k: jax.random.fold_in(k, p))(bkeys))
        for p, sp in enumerate(block)
    )
    if cfg.family == "encdec":
        enc_spec = LayerSpec("attn", None, "gelu2", cross=False)
        ekeys = jax.random.split(keys[4], cfg.encoder_layers)
        params["encoder"] = jax.vmap(lambda k: _init_layer(k, cfg, enc_spec, dtype))(ekeys)
        params["enc_final_norm"] = jnp.zeros((d,), jnp.float32)
        params["enc_pos"] = L.dense_init(keys[5], (cfg.encoder_seq, d), dtype)
        params["pos_embed"] = L.dense_init(keys[6], (max(max_seq, 1), d), dtype)
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------
def _apply_mlp(p, spec: LayerSpec, cfg, x, decode: bool):
    if spec.mlp == "moe":
        fn = M.moe_decode if decode else M.moe_forward
        y, aux = fn(p, cfg, x)
        return y, aux
    if spec.mlp == "gelu2":
        return jax.nn.gelu(x @ p["wi"]) @ p["wo"], 0.0
    return L.mlp(p, x), 0.0


def apply_layer(p, cfg, spec: LayerSpec, x, positions, enc_out=None,
                kernels="reference"):
    """Full-sequence pass. Returns (x, cache_entry, aux).

    ``kernels`` (a resolved ``repro.kernels.policy`` mode) routes the mixer
    hot spots through the Pallas kernels (flash attention / ssd_scan) when
    it is ``pallas`` or ``interpret``; MLA keeps the reference path — its
    latent expansion has no kernel counterpart yet.
    """
    with named_scope(SSM if spec.mixer == "ssm" else ATTN):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        use_rope = cfg.family != "encdec"
        if spec.mixer == "attn":
            o, cache = L.attn_forward(p["mixer"], cfg, h, positions,
                                      window=spec.window, use_rope=use_rope,
                                      kernels=kernels)
        elif spec.mixer == "mla":
            o, cache = L.mla_forward(p["mixer"], cfg, h, positions)
        else:
            o, cache = S.ssm_forward(p["mixer"], cfg, h, kernels=kernels)
        # tag the row-parallel projection outputs: under remat_policy="tp_out"
        # these (post-all-reduce) activations are SAVED, so the backward pass
        # does not re-run the forward TP all-reduces (§Perf)
        o = jax.ad_checkpoint.checkpoint_name(o, "tp_out")
        x = x + o
    if spec.cross:
        hx = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
        ck = (enc_out @ p["cross"]["wk"]).reshape(
            enc_out.shape[0], enc_out.shape[1], cfg.num_kv_heads, cfg.head_dim)
        cv = (enc_out @ p["cross"]["wv"]).reshape(
            enc_out.shape[0], enc_out.shape[1], cfg.num_kv_heads, cfg.head_dim)
        o = L.cross_attn_forward(p["cross"], cfg, hx, enc_out)
        x = x + o
        cache = cache + (ck, cv)
    if spec.mlp != "none":
        with named_scope(MLP):
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            y, aux = _apply_mlp(p["mlp"], spec, cfg, h, decode=False)
            y = jax.ad_checkpoint.checkpoint_name(y, "tp_out")
            x = x + y
    else:
        aux = 0.0
    return constrain(x, "hidden"), cache, aux


def apply_layer_decode(p, cfg, spec: LayerSpec, x, cache, t):
    """One-token pass. cache is this layer's entry; returns (x, cache, aux)."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    use_rope = cfg.family != "encdec"
    if spec.mixer == "attn":
        o, ck, cv = L.attn_decode(p["mixer"], cfg, h, cache[0], cache[1], t,
                                  window=spec.window, use_rope=use_rope)
        new_cache = (ck, cv) + tuple(cache[2:])
    elif spec.mixer == "mla":
        o, ckv, krope = L.mla_decode(p["mixer"], cfg, h, cache[0], cache[1], t)
        new_cache = (ckv, krope)
    else:
        o, conv_s, ssd_s = S.ssm_decode(p["mixer"], cfg, h, cache[0], cache[1])
        new_cache = (conv_s, ssd_s)
    x = x + o
    if spec.cross:
        hx = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
        ck, cv = cache[2], cache[3]
        q = (hx @ p["cross"]["wq"]).reshape(x.shape[0], 1, cfg.num_heads, cfg.head_dim)
        o = L.decode_attend(q, ck, cv, ck.shape[1] - 1, window=None)
        o = o.reshape(x.shape[0], 1, cfg.num_heads * cfg.head_dim) @ p["cross"]["wo"]
        x = x + o
    if spec.mlp == "none":
        return x, new_cache, 0.0
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    y, aux = _apply_mlp(p["mlp"], spec, cfg, h, decode=True)
    return x + y, new_cache, aux


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------
def encoder_forward(params, cfg, frames):
    """frames: (B, Se, d) stub embeddings -> (B, Se, d)."""
    x = frames + params["enc_pos"][None, :frames.shape[1]]
    spec = LayerSpec("attn", None, "gelu2", cross=False)

    def body(x, lp):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        B, Se, _ = h.shape
        H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = (h @ lp["mixer"]["wq"]).reshape(B, Se, H, hd)
        k = (h @ lp["mixer"]["wk"]).reshape(B, Se, K, hd)
        v = (h @ lp["mixer"]["wv"]).reshape(B, Se, K, hd)
        o = L._attend_chunked(q, k, v, causal=False, window=None,
                              q_chunk=min(L.DEFAULT_Q_CHUNK, Se))
        x = x + o.reshape(B, Se, H * hd) @ lp["mixer"]["wo"]
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = _apply_mlp(lp["mlp"], spec, cfg, h, decode=False)
        return x + y, None

    x, _ = jax.lax.scan(body, x, params["encoder"], unroll=scan_unroll())
    return L.rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# full-sequence forward
# ---------------------------------------------------------------------------
def _embed(params, cfg, tokens, frontend_embeds=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "vlm" and frontend_embeds is not None:
        n = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x[:, n:]], axis=1)
    if cfg.family == "encdec":
        x = x + params["pos_embed"][None, :tokens.shape[1]]
    return constrain(x, "hidden")


def forward(params, cfg, tokens, frontend_embeds=None, *, want_cache=False,
            remat=True, remat_policy="full", kernels="reference"):
    """-> (hidden (B,S,d), caches or None, aux)."""
    prefix_specs, block_specs, n_blocks = stack_plan(cfg)
    B, Sq = tokens.shape
    # positions as (1, S): broadcasting into rope stays replicated under
    # GSPMD (a (B, S) positions tensor gets batch-sharded and breeds
    # partial-sum all-reduces of the cos/sin tables — §Perf)
    positions = jnp.arange(Sq)[None, :]
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encoder_forward(params, cfg, frontend_embeds)
    x = _embed(params, cfg, tokens, frontend_embeds)

    prefix_caches, aux_total = [], 0.0
    for sp, lp in zip(prefix_specs, params["prefix"]):
        x, cache, aux = apply_layer(lp, cfg, sp, x, positions, enc_out,
                                    kernels=kernels)
        aux_total += aux
        prefix_caches.append(cache)

    def block_body(carry, block_params):
        x, aux = carry
        caches = []
        for p, sp in enumerate(block_specs):
            x, cache, a = apply_layer(block_params[p], cfg, sp, x, positions,
                                      enc_out, kernels=kernels)
            aux += a
            caches.append(cache)
        ys = tuple(caches) if want_cache else None
        return (x, aux), ys

    if remat and not want_cache:
        if remat_policy == "tp_out":
            policy = jax.checkpoint_policies.save_only_these_names("tp_out")
            body = jax.checkpoint(block_body, policy=policy)
        else:
            body = jax.checkpoint(block_body)
    else:
        body = block_body
    (x, aux_total), block_caches = jax.lax.scan(
        body, (x, aux_total), params["blocks"], unroll=scan_unroll())
    with named_scope(LM_HEAD):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    caches = (prefix_caches, block_caches) if want_cache else None
    return x, caches, aux_total


def logits_head(params, cfg, h):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (h @ w).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    return constrain(logits, "logits")


# ---------------------------------------------------------------------------
# loss (chunked over sequence; mirrors kernels/fused_xent)
# ---------------------------------------------------------------------------
def chunked_xent(params, cfg, h, labels, mask, chunk: int = LOSS_CHUNK):
    """h: (B,S,d); labels/mask: (B,S). Returns (sum_nll, sum_mask)."""
    B, Sq, d = h.shape
    c = min(chunk, Sq)
    while Sq % c:                 # largest dividing chunk <= requested
        c -= 1
    n = Sq // c
    hr = jnp.moveaxis(h.reshape(B, n, c, d), 1, 0)
    yr = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)
    mr = jnp.moveaxis(mask.reshape(B, n, c), 1, 0)

    def body(carry, inp):
        tot, cnt = carry
        hc, yc, mc = inp
        logits = logits_head(params, cfg, hc)               # (B,c,Vp) f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via masked reduction, NOT take_along_axis: a gather
        # along the vocab-sharded axis forces GSPMD to all-gather the full
        # logits (§Perf); the where+sum partitions cleanly.
        col = jnp.arange(logits.shape[-1])
        gold = jnp.sum(jnp.where(col == yc[..., None], logits, 0.0), axis=-1)
        nll = (lse - gold) * mc
        return (tot + nll.sum(), cnt + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (hr, yr, mr),
                                 unroll=scan_unroll())
    return tot, cnt


def lm_loss_fn(params, cfg, batch, *, aux_weight=0.01, remat=True,
               remat_policy="full", kernels="reference"):
    """Next-token CE averaged over valid positions. batch: {'tokens', ...}.

    Returns f32 ``(total_loss, data_loss)`` scalars regardless of the
    compute dtype — ψ statistics and the SPC queue are f32 by contract
    (the head matmul runs in f32 either way; this pins the output dtype).
    """
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    h, _, aux = forward(params, cfg, tokens, fe, want_cache=False, remat=remat,
                        remat_policy=remat_policy, kernels=kernels)
    with named_scope(LM_HEAD):
        labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
        if cfg.family == "vlm":
            n = cfg.num_image_tokens
            mask = mask.at[:, :n].set(0.0)
        if kernels != "reference":
            from repro.kernels.fused_xent.ops import fused_xent_sum
            w = params["embed"].T if cfg.tie_embeddings else params["head"]
            tot, cnt = fused_xent_sum(h, w, labels, mask, cfg.vocab_size,
                                      kernels == "interpret")
        else:
            tot, cnt = chunked_xent(params, cfg, h, labels, mask)
        loss = (tot / jnp.maximum(cnt, 1.0)).astype(jnp.float32)
    return loss + aux_weight * jnp.asarray(aux, jnp.float32), loss


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------
def init_cache(cfg, B: int, S: int, dtype=jnp.bfloat16):
    """Zero caches for every (prefix, block-position) layer."""
    prefix_specs, block_specs, n_blocks = stack_plan(cfg)

    def entry(sp: LayerSpec, stacked: bool):
        lead = (n_blocks,) if stacked else ()
        if sp.mixer == "attn":
            e = (jnp.zeros(lead + (B, S, cfg.num_kv_heads, cfg.head_dim), dtype),
                 jnp.zeros(lead + (B, S, cfg.num_kv_heads, cfg.head_dim), dtype))
        elif sp.mixer == "mla":
            e = (jnp.zeros(lead + (B, S, cfg.kv_lora_rank), dtype),
                 jnp.zeros(lead + (B, S, cfg.qk_rope_head_dim), dtype))
        else:
            e = (jnp.zeros(lead + (B, cfg.conv_width - 1, S_conv(cfg)), dtype),
                 jnp.zeros(lead + (B, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state),
                           jnp.float32))
        if sp.cross:
            e = e + (jnp.zeros(lead + (B, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype),
                     jnp.zeros(lead + (B, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype))
        return e

    prefix_cache = [entry(sp, False) for sp in prefix_specs]
    block_cache = tuple(entry(sp, True) for sp in block_specs)
    return {"prefix": prefix_cache, "blocks": block_cache,
            "t": jnp.zeros((), jnp.int32)}


def S_conv(cfg):
    return S.conv_channels(cfg)


def decode_step(params, cfg, cache, tokens):
    """One decode step. tokens: (B, 1) -> (logits (B, Vp), new cache).

    ``cache['t']`` is a scalar (one-shot serving: every row at the same
    position) or a (B,) vector of per-row cursors (slot-based continuous
    batching — ``repro.serve.slots``); the layer decode paths accept both.
    """
    prefix_specs, block_specs, n_blocks = stack_plan(cfg)
    t = cache["t"]
    if jnp.ndim(t) == 1 and cfg.family == "encdec":
        raise NotImplementedError(
            "per-slot decode cursors are not supported for enc-dec configs "
            "(learned pos_embed lookup + cross-attention assume one shared "
            "position)")
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "encdec":
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], t, 1, axis=0)[None, 0:1]
    x = constrain(x, "decode_hidden")

    new_prefix = []
    for sp, lp, ce in zip(prefix_specs, params["prefix"], cache["prefix"]):
        x, ce, _ = apply_layer_decode(lp, cfg, sp, x, ce, t)
        new_prefix.append(ce)

    def block_body(x, inp):
        block_params, block_cache = inp
        new_entries = []
        for p, sp in enumerate(block_specs):
            x, ce, _ = apply_layer_decode(block_params[p], cfg, sp, x,
                                          block_cache[p], t)
            new_entries.append(ce)
        return x, tuple(new_entries)

    x, new_blocks = jax.lax.scan(block_body, x, (params["blocks"], cache["blocks"]),
                                 unroll=scan_unroll())
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, cfg, x)[:, 0]
    new_cache = {"prefix": new_prefix, "blocks": new_blocks, "t": t + 1}
    return logits, new_cache


def prefill(params, cfg, tokens, frontend_embeds=None):
    """Full-sequence prefill -> (last-token logits, caches-as-scan-stacked)."""
    h, caches, _ = forward(params, cfg, tokens, frontend_embeds,
                           want_cache=True, remat=False)
    logits = logits_head(params, cfg, h[:, -1:])[:, 0]
    return logits, caches
