"""Unified model API: every architecture exposes the same surface.

``build_model(cfg)`` -> ``Model`` with:
  init(key, shape)           -> params (real arrays; use jax.eval_shape for abstract)
  loss_fn(params, batch)     -> (total_loss, data_loss)   [train]
  prefill_fn(params, batch)  -> (last logits, caches)     [inference-prefill]
  decode_fn(params, cache, tokens) -> (logits, cache)     [inference-decode]
  init_cache(B, S)           -> zero caches
  input_specs(shape)         -> {name: ShapeDtypeStruct} for train/prefill/decode
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import transformer as T


@dataclass
class Model:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    prefill_fn: Callable
    decode_fn: Callable
    init_cache: Callable
    input_specs: Callable


def _frontend_spec(cfg, B):
    if cfg.family == "vlm":
        return jax.ShapeDtypeStruct((B, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family in ("encdec", "audio"):
        return jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return None


def build_model(cfg: ModelConfig, *, remat: bool = True,
                remat_policy: str = "full",
                kernels: str = "reference",
                param_dtype=jnp.bfloat16) -> Model:
    """``kernels`` ∈ {'pallas', 'reference', 'interpret'} picks the step-body
    hot-spot implementations (``repro.kernels.policy``): 'pallas' needs a
    TPU backend and raises elsewhere; 'interpret' runs the same kernels
    through the Pallas interpreter (a correctness harness, not a training
    path).  The choice is baked at build time — one HLO per model, no
    in-step branching.

    ``param_dtype`` is the mixed-precision policy's compute dtype (params +
    activations; bf16 default).  Norm scales, ψ statistics, the loss scalars
    and the SPC queue stay f32 regardless — see ``T.lm_loss_fn`` and
    ``trainer.make_loss_and_grad``.
    """
    from repro.kernels.policy import resolve_kernels
    kernels = resolve_kernels(kernels)

    def init(key, max_seq: int = 4096):
        return T.init_params(key, cfg, max_seq=max_seq, dtype=param_dtype)

    def loss_fn(params, batch):
        return T.lm_loss_fn(params, cfg, batch, remat=remat,
                            remat_policy=remat_policy, kernels=kernels)

    def prefill_fn(params, batch):
        return T.prefill(params, cfg, batch["tokens"],
                         batch.get("frontend_embeds"))

    def decode_fn(params, cache, tokens):
        return T.decode_step(params, cfg, cache, tokens)

    def init_cache(B, S):
        return T.init_cache(cfg, B, S)

    def input_specs(shape: InputShape):
        B, S = shape.global_batch, shape.seq_len
        specs = {"tokens": jax.ShapeDtypeStruct(
            (B, 1 if shape.kind == "decode" else S), jnp.int32)}
        fe = _frontend_spec(cfg, B)
        if fe is not None and shape.kind != "decode":
            specs["frontend_embeds"] = fe
        return specs

    return Model(cfg, init, loss_fn, prefill_fn, decode_fn, init_cache,
                 input_specs)
