"""Model facade: embeddings + stack + head, loss, prefill/decode entrypoints."""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer

Array = jax.Array

MOE_AUX_WEIGHT = 0.01


def embed_inputs(cfg: ModelConfig, params, tokens: Array,
                 frontend_embeds: Optional[Array] = None,
                 frontend_mask: Optional[Array] = None) -> Array:
    """Token embeddings, with the modality-stub injection points.

    vision (internvl2): positions where ``frontend_mask`` is set take the
    precomputed patch embeddings instead of the token embedding.
    audio (musicgen): precomputed frame/conditioning embeddings are *added*
    to the EnCodec-token embeddings.
    """
    h = params["embed"][tokens]
    if frontend_embeds is not None:
        fe = frontend_embeds.astype(h.dtype)
        if cfg.frontend == "vision":
            assert frontend_mask is not None
            h = jnp.where(frontend_mask[..., None], fe, h)
        elif cfg.frontend == "audio":
            h = h + fe
        else:
            raise ValueError(f"{cfg.name} has no frontend but got embeds")
    return h


def lm_logits(cfg: ModelConfig, params, h: Array) -> Array:
    """Project to the (padded) vocabulary; pad slots masked to −inf."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
    if cfg.padded_vocab > cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


def _forward_counted(cfg: ModelConfig, params, tokens: Array,
                     frontend_embeds=None, frontend_mask=None):
    """→ (logits [B, S, V], aux, the stack's counters)."""
    h = embed_inputs(cfg, params, tokens, frontend_embeds, frontend_mask)
    h, _, aux, counters = transformer.run_stack(cfg, params, h)
    h = transformer.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return lm_logits(cfg, params, h), aux, counters


def forward(cfg: ModelConfig, params, tokens: Array,
            frontend_embeds=None, frontend_mask=None) -> tuple[Array, Array]:
    """Teacher-forcing forward. tokens [B, S] → (logits [B, S, V], aux)."""
    return _forward_counted(cfg, params, tokens, frontend_embeds,
                            frontend_mask)[:2]


def loss_fn(cfg: ModelConfig, params, batch: dict) -> tuple[Array, dict]:
    """Mean next-token cross-entropy (f32) + MoE aux. batch: tokens, labels.

    The metrics hold ``counters``, what the model counted (hybrid_moe:
    ``routed_slots``, ``expert_rows``; empty for every other family)."""
    logits, aux, counters = _forward_counted(
        cfg, params, batch["tokens"], batch.get("frontend_embeds"),
        batch.get("frontend_mask"))
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    ce = -jnp.mean(ll)
    total = ce + MOE_AUX_WEIGHT * aux
    return total, {"ce": ce, "aux": aux, "counters": counters}


def prefill(cfg: ModelConfig, params, tokens: Array, cache,
            frontend_embeds=None, frontend_mask=None):
    """Process a full prompt, seeding the cache. → (last_logits [B,V], cache)."""
    h = embed_inputs(cfg, params, tokens, frontend_embeds, frontend_mask)
    h, new_cache, _, _ = transformer.run_stack(cfg, params, h, cache=cache)
    h = transformer.rms_norm(h[:, -1:], params["final_norm"], cfg.norm_eps)
    return lm_logits(cfg, params, h)[:, 0], new_cache


def decode_step(cfg: ModelConfig, params, cache, token: Array, pos: Array):
    """One decode step. token [B] int32, pos scalar → (logits [B,V], cache)."""
    h = params["embed"][token][:, None, :]               # [B, 1, D]
    h, new_cache, _, _ = transformer.run_stack(cfg, params, h, cache=cache,
                                            pos=pos)
    h = transformer.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return lm_logits(cfg, params, h)[:, 0], new_cache


init_params = transformer.init_params
param_specs = transformer.param_specs
init_cache = transformer.init_cache
cache_specs = transformer.cache_specs
