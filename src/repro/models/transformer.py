"""Decoder stacks for all assigned families (dense/moe/ssm/hybrid/hybrid_moe/
vlm/audio).

Scan-over-layers with stacked parameters keeps the HLO size O(1) in depth —
essential for 40-cell × 2-mesh dry-run compile times — with optional remat
of the scan body. The hybrid (zamba2-style) stack is structured as
``n_sites`` super-blocks (attn_every mamba layers + one *shared* attention
block) plus trailing mamba layers, so the shared block's KV cache is
per-site, not per-layer (DESIGN §4). The hybrid_moe (Nemotron-H) stack
follows ``cfg.layer_pattern``: one pre-norm block per character, each with
one mixer (M Mamba2, E MoE, * attention); its blocks differ in kind, so they
are a list, unrolled, each block checkpointed.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm
from repro.models.attention import run_attention
from repro.models.layers import dense_init, embed_init, rms_norm, swiglu
from repro.models.moe import moe_dropless, moe_ffn
from repro.obs import scopes

Array = jax.Array
Params = Any


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, dtype):
    d, hq, hkv, dh = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * dh, dtype),
        "wk": dense_init(ks[1], d, hkv * dh, dtype),
        "wv": dense_init(ks[2], d, hkv * dh, dtype),
        "wo": dense_init(ks[3], hq * dh, d, dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def _mlp_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    p = {
        "w_up": dense_init(ks[1], d, f, dtype),
        "w_down": dense_init(ks[2], f, d, dtype),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = dense_init(ks[0], d, f, dtype)
    return p


def _mlp_apply(cfg: ModelConfig, params, x):
    if cfg.mlp_type == "swiglu":
        return swiglu(x, params["w_gate"], params["w_up"], params["w_down"])
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["w_up"]))
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


def _moe_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    def experts(k, din, dout):
        return (jax.vmap(lambda kk: dense_init(kk, din, dout, dtype))
                (jax.random.split(k, e)))
    return {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": experts(ks[1], d, f),
        "w_up": experts(ks[2], d, f),
        "w_down": experts(ks[3], f, d),
    }


def _dense_layer_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "attn": _attn_init(ks[0], cfg, dtype),
        "mlp": (_moe_init(ks[1], cfg, dtype) if cfg.family == "moe"
                else _mlp_init(ks[1], cfg, dtype)),
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }


def _mamba_layer_init(key, cfg: ModelConfig, dtype):
    return {
        "mamba": ssm.mamba2_init(key, cfg, dtype),
        "ln": jnp.ones((cfg.d_model,), dtype),
    }


def _expert_share_init(key, cfg: ModelConfig, dtype):
    """Router over every expert; this chip's held experts (relu², two
    matrices each) and the shared expert."""
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.held_experts

    def experts(k, din, dout):
        return (jax.vmap(lambda kk: dense_init(kk, din, dout, dtype))
                (jax.random.split(k, e)))
    p = {
        "router": dense_init(ks[0], d, cfg.num_experts, dtype),
        "w_up": experts(ks[1], d, f),
        "w_down": experts(ks[2], f, d),
    }
    if cfg.shared_d_ff:
        p["shared_up"] = dense_init(ks[3], d, cfg.shared_d_ff, dtype)
        p["shared_down"] = dense_init(ks[4], cfg.shared_d_ff, d, dtype)
    return p


def _pattern_block_init(key, cfg: ModelConfig, kind: str, dtype):
    mixer = {"M": ("mamba", lambda k: ssm.mamba2_init(k, cfg, dtype)),
             "E": ("moe", lambda k: _expert_share_init(k, cfg, dtype)),
             "*": ("attn", lambda k: _attn_init(k, cfg, dtype))}[kind]
    return {mixer[0]: mixer[1](key), "ln": jnp.ones((cfg.d_model,), dtype)}


# ---------------------------------------------------------------------------
# Per-layer apply
# ---------------------------------------------------------------------------

def _dense_layer(cfg: ModelConfig, params, h, cache=None, pos=None):
    """Pre-LN transformer layer; returns (h, new_cache, aux)."""
    x = rms_norm(h, params["ln1"], cfg.norm_eps)
    o, new_cache = run_attention(
        params["attn"], x, cfg_heads=cfg.num_heads, cfg_kv=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        window=cfg.sliding_window, cache=cache, pos=pos)
    h = h + o
    x = rms_norm(h, params["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_ffn(params["mlp"], x, num_experts=cfg.num_experts,
                         top_k=cfg.num_experts_per_tok,
                         capacity_factor=cfg.capacity_factor)
    else:
        y = _mlp_apply(cfg, params["mlp"], x)
        aux = jnp.float32(0)
    return h + y, new_cache, aux


def _mamba_layer(cfg: ModelConfig, params, h, cache=None):
    x = rms_norm(h, params["ln"], cfg.norm_eps)
    y, new_cache = ssm.mamba2_block(params["mamba"], cfg, x, cache)
    return h + y, new_cache


def _pattern_block(cfg: ModelConfig, kind: str, params, h, cache=None,
                   pos=None):
    """One hybrid_moe block: h + mixer(rmsnorm(h)).
    → (h, new_cache, counters)."""
    x = rms_norm(h, params["ln"], cfg.norm_eps)
    counters = {}
    if kind == "M":
        y, new_cache = ssm.mamba2_block(params["mamba"], cfg, x, cache)
    elif kind == "*":
        y, new_cache = run_attention(
            params["attn"], x, cfg_heads=cfg.num_heads,
            cfg_kv=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta, window=cfg.sliding_window,
            cache=cache, pos=pos, rope=cfg.rope, remat_blocks=cfg.remat)
    else:
        with jax.named_scope(scopes.MOE):
            y, counters = moe_dropless(
                params["moe"], x, top_k=cfg.num_experts_per_tok,
                first=cfg.experts_first, held=cfg.held_experts,
                routed_scale=cfg.routed_scale)
        new_cache = None
    return h + y, new_cache, counters


# ---------------------------------------------------------------------------
# Stack init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    dtype = cfg.dtype
    k_embed, k_layers, k_shared, k_head = jax.random.split(key, 4)
    params: dict = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model,
                                       cfg.padded_vocab, dtype)
    L = cfg.num_layers
    if cfg.layer_pattern:
        params["layers"] = [
            _pattern_block_init(k, cfg, kind, dtype)
            for k, kind in zip(jax.random.split(k_layers, L),
                               cfg.layer_pattern)]
    elif cfg.family in ("ssm",):
        params["layers"] = jax.vmap(
            lambda k: _mamba_layer_init(k, cfg, dtype))(
                jax.random.split(k_layers, L))
    elif cfg.family == "hybrid":
        n_sites = L // cfg.attn_every
        trailing = L - n_sites * cfg.attn_every
        site_keys = jax.random.split(k_layers, n_sites * cfg.attn_every)
        site_params = jax.vmap(lambda k: _mamba_layer_init(k, cfg, dtype))(
            site_keys)
        params["layers"] = jax.tree.map(
            lambda a: a.reshape(n_sites, cfg.attn_every, *a.shape[1:]),
            site_params)
        if trailing:
            params["trailing"] = jax.vmap(
                lambda k: _mamba_layer_init(k, cfg, dtype))(
                    jax.random.split(jax.random.fold_in(k_layers, 1),
                                     trailing))
        ks = jax.random.split(k_shared, 2)
        params["shared_attn"] = {
            "attn": _attn_init(ks[0], cfg, dtype),
            "mlp": _mlp_init(ks[1], cfg, dtype),
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
        }
    else:  # dense / moe / vlm / audio share the dense-stack structure
        params["layers"] = jax.vmap(
            lambda k: _dense_layer_init(k, cfg, dtype))(
                jax.random.split(k_layers, L))
    return params


def param_specs(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree of the params (dry-run: no allocation)."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    dtype = cfg.dtype
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    # SWA needs only the last `window` tokens → ring buffer (attention.py)
    eff_len = (min(max_len, cfg.sliding_window) if cfg.sliding_window > 0
               else max_len)

    def attn_cache():
        return {"k": jnp.zeros((batch, eff_len, hkv, dh), dtype),
                "v": jnp.zeros((batch, eff_len, hkv, dh), dtype)}

    if cfg.layer_pattern:
        kinds = {"M": lambda: ssm.mamba2_cache_init(cfg, batch, dtype),
                 "*": attn_cache, "E": lambda: None}
        return {"layers": [kinds[k]() for k in cfg.layer_pattern]}
    if cfg.family == "ssm":
        one = ssm.mamba2_cache_init(cfg, batch, dtype)
        return {"layers": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)), one)}
    if cfg.family == "hybrid":
        n_sites = cfg.num_layers // cfg.attn_every
        trailing = cfg.num_layers - n_sites * cfg.attn_every
        one = ssm.mamba2_cache_init(cfg, batch, dtype)
        cache = {
            "layers": jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (n_sites, cfg.attn_every, *a.shape)), one),
            "shared": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_sites, *a.shape)),
                attn_cache()),
        }
        if trailing:
            cache["trailing"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (trailing, *a.shape)), one)
        return cache
    return {"layers": jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)),
        attn_cache())}


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


# ---------------------------------------------------------------------------
# Stack apply
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _remat_groups(cfg: ModelConfig, num_layers: int) -> int:
    """Largest divisor of L that is ≤ ⌈√L⌉ (√L checkpointing group count)."""
    if not (cfg.remat and cfg.nested_remat) or num_layers < 4:
        return 1
    import math
    cap = math.isqrt(num_layers - 1) + 1
    best = 1
    for g in range(2, cap + 1):
        if num_layers % g == 0:
            best = g
    return best


def _scan_layers(cfg: ModelConfig, body, carry, stacked):
    """Scan over stacked layer params with optional √L nested remat.

    ``body(carry, layer_params) → carry`` (no per-layer outputs — used by
    the no-cache training path where only the carry matters).
    """
    leaves = jax.tree.leaves(stacked)
    num_layers = leaves[0].shape[0]
    g = _remat_groups(cfg, num_layers)

    def body_scan(c, p_i):
        return body(c, p_i), None

    if g == 1:
        carry, _ = jax.lax.scan(_maybe_remat(body_scan, cfg), carry, stacked)
        return carry

    grouped = jax.tree.map(
        lambda a: a.reshape(g, num_layers // g, *a.shape[1:]), stacked)

    def group_body(c, group_params):
        c, _ = jax.lax.scan(_maybe_remat(body_scan, cfg), c, group_params)
        return c, None

    carry, _ = jax.lax.scan(_maybe_remat(group_body, cfg), carry, grouped)
    return carry


def _shared_block(cfg: ModelConfig, params, h, cache=None, pos=None):
    """Zamba2-style shared transformer block (attn + MLP)."""
    x = rms_norm(h, params["ln1"], cfg.norm_eps)
    o, new_cache = run_attention(
        params["attn"], x, cfg_heads=cfg.num_heads, cfg_kv=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        window=cfg.sliding_window, cache=cache, pos=pos)
    h = h + o
    x = rms_norm(h, params["ln2"], cfg.norm_eps)
    y = _mlp_apply(cfg, params["mlp"], x)
    return h + y, new_cache


def _run_pattern(cfg: ModelConfig, params, h: Array, cache=None,
                 pos: Optional[Array] = None):
    """The hybrid_moe stack. → (h, new_cache, counters summed over the
    blocks)."""
    counters: dict = {}
    new_caches = []
    for i, kind in enumerate(cfg.layer_pattern):
        block = functools.partial(_pattern_block, cfg, kind)
        c_i = None if cache is None else cache["layers"][i]
        if cache is None and cfg.remat:
            block = jax.checkpoint(block)
        h, c_new, cnt = block(params["layers"][i], h, c_i, pos)
        new_caches.append(c_new)
        for name, v in cnt.items():
            counters[name] = counters.get(name, 0) + v
    return h, (None if cache is None else {"layers": new_caches}), counters


def run_stack(cfg: ModelConfig, params, h: Array, cache=None,
              pos: Optional[Array] = None):
    """h: [B, S, D] embeddings → (h, new_cache, aux, counters). cache/pos
    per decode. ``counters`` are what the model counted (hybrid_moe:
    ``routed_slots``, ``expert_rows`` summed over its E blocks; empty for
    every other family)."""
    if cfg.layer_pattern:
        h, new_cache, counters = _run_pattern(cfg, params, h, cache, pos)
        return h, new_cache, jnp.float32(0), counters
    return (*_run_uniform(cfg, params, h, cache, pos), {})


def _run_uniform(cfg: ModelConfig, params, h: Array, cache=None,
                 pos: Optional[Array] = None):
    """The stacks of one repeated layer kind. → (h, new_cache, aux)."""
    aux_total = jnp.float32(0)

    if cfg.family == "ssm":
        def body(carry, xs):
            hh = carry
            p_i, c_i = xs
            hh, c_new = _mamba_layer(cfg, p_i, hh, c_i)
            return hh, c_new
        caches = None if cache is None else cache["layers"]
        if caches is None:
            h = _scan_layers(
                cfg, lambda hh, p_i: _mamba_layer(cfg, p_i, hh, None)[0],
                h, params["layers"])
            return h, None, aux_total
        h, new_caches = jax.lax.scan(_maybe_remat(body, cfg), h,
                                     (params["layers"], caches))
        return h, {"layers": new_caches}, aux_total

    if cfg.family == "hybrid":
        n_sites = cfg.num_layers // cfg.attn_every
        trailing = cfg.num_layers - n_sites * cfg.attn_every
        new_cache = {"layers": [], "shared": []} if cache is not None else None

        def mamba_scan(hh, stacked, caches):
            if caches is None:
                def body(hh, p_i):
                    hh, _ = _mamba_layer(cfg, p_i, hh, None)
                    return hh, None
                hh, _ = jax.lax.scan(_maybe_remat(body, cfg), hh, stacked)
                return hh, None
            def body(hh, xs):
                p_i, c_i = xs
                hh, c_new = _mamba_layer(cfg, p_i, hh, c_i)
                return hh, c_new
            hh, c_new = jax.lax.scan(_maybe_remat(body, cfg), hh,
                                     (stacked, caches))
            return hh, c_new

        for site in range(n_sites):
            site_params = jax.tree.map(lambda a: a[site], params["layers"])
            site_cache = (None if cache is None else
                          jax.tree.map(lambda a: a[site], cache["layers"]))
            h, c_new = mamba_scan(h, site_params, site_cache)
            sh_cache = (None if cache is None else
                        jax.tree.map(lambda a: a[site], cache["shared"]))
            h, sh_new = _shared_block(cfg, params["shared_attn"], h,
                                      sh_cache, pos)
            if cache is not None:
                new_cache["layers"].append(c_new)
                new_cache["shared"].append(sh_new)
        if trailing:
            tr_cache = None if cache is None else cache["trailing"]
            h, tr_new = mamba_scan(h, params["trailing"], tr_cache)
            if cache is not None:
                new_cache["trailing"] = tr_new
        if cache is not None:
            new_cache["layers"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *new_cache["layers"])
            new_cache["shared"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *new_cache["shared"])
        return h, new_cache, aux_total

    # dense / moe / vlm / audio
    if cache is None:
        def body(carry, p_i):
            hh, aux = carry
            hh, _, a = _dense_layer(cfg, p_i, hh, None, None)
            return (hh, aux + a)
        h, aux_total = _scan_layers(cfg, body, (h, aux_total),
                                    params["layers"])
        return h, None, aux_total

    def body(carry, xs):
        hh, aux = carry
        p_i, c_i = xs
        hh, c_new, a = _dense_layer(cfg, p_i, hh, c_i, pos)
        return (hh, aux + a), c_new
    (h, aux_total), new_caches = jax.lax.scan(
        _maybe_remat(body, cfg), (h, aux_total),
        (params["layers"], cache["layers"]))
    return h, {"layers": new_caches}, aux_total
