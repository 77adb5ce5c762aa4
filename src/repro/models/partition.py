"""Sharding rules: PartitionSpecs for params, caches and batches.

Rules are divisibility-aware (DESIGN §5): a dim is sharded over the
``model`` axis only when it divides evenly AND the sharding is head-aligned
where heads matter; otherwise the leaf stays replicated over ``model`` and
GSPMD shards the *computation* along batch/seq instead. Batch shards over
(``pod``, ``data``); long-context decode (batch 1) shards the KV-cache
sequence dim over ``data`` (split-K decode).

Everything here returns specs for **pjit auto mode** — the manual ring in
``core/ring.py`` has its own flat-space layout and never consumes these.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def batch_axes(mesh) -> tuple:
    """Mesh axes used for data parallelism ((pod, data) when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _model_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def param_pspecs(cfg: ModelConfig, mesh) -> Any:
    """PartitionSpec pytree matching ``transformer.init_params`` output."""
    m = _model_size(mesh)
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    def attn_specs():
        # head-aligned TP: shard projections only if the head count divides
        q_ok = _div(hq, m)
        kv_ok = _div(hkv, m)
        s = {
            "wq": P(None, "model") if q_ok else P(None, None),
            "wk": P(None, "model") if kv_ok else P(None, None),
            "wv": P(None, "model") if kv_ok else P(None, None),
            "wo": P("model", None) if q_ok else P(None, None),
        }
        if cfg.attn_bias:
            s["bq"] = P("model") if q_ok else P(None)
            s["bk"] = P("model") if kv_ok else P(None)
            s["bv"] = P("model") if kv_ok else P(None)
        return s

    def mlp_specs():
        f_ok = _div(cfg.d_ff, m)
        s = {
            "w_up": P(None, "model") if f_ok else P(None, None),
            "w_down": P("model", None) if f_ok else P(None, None),
        }
        if cfg.mlp_type == "swiglu":
            s["w_gate"] = s["w_up"]
        return s

    def moe_specs():
        f_ok = _div(cfg.d_ff, m)
        return {
            "router": P(None, None),
            "w_gate": P(None, None, "model") if f_ok else P(None, None, None),
            "w_up": P(None, None, "model") if f_ok else P(None, None, None),
            "w_down": P(None, "model", None) if f_ok else P(None, None, None),
        }

    def mamba_specs():
        # mixed-group in_proj concat dim → replicated over model (DESIGN §5)
        s = {
            "in_proj": P(None, None), "conv_w": P(None, None),
            "dt_bias": P(None), "a_log": P(None), "d_skip": P(None),
            "norm": P(None), "out_proj": P(None, None),
        }
        if cfg.ssm_conv_bias:
            s["conv_b"] = P(None)
        return s

    def expert_share_specs():
        # expert parallelism: the held experts split over `model`
        e_ok = _div(cfg.held_experts, m)
        ex = "model" if e_ok else None
        s = {"router": P(None, None), "w_up": P(ex, None, None),
             "w_down": P(ex, None, None)}
        if cfg.shared_d_ff:
            f_ok = _div(cfg.shared_d_ff, m)
            s["shared_up"] = P(None, "model") if f_ok else P(None, None)
            s["shared_down"] = P("model", None) if f_ok else P(None, None)
        return s

    def stack(spec, extra_lead=1):
        return jax.tree.map(
            lambda s: P(*([None] * extra_lead), *s), spec,
            is_leaf=lambda x: isinstance(x, P))

    v_ok = _div(cfg.padded_vocab, m)
    specs: dict = {
        "embed": P("model", None) if v_ok else P(None, None),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model") if v_ok else P(None, None)

    if cfg.layer_pattern:
        mixers = {"M": ("mamba", mamba_specs),
                  "E": ("moe", expert_share_specs), "*": ("attn", attn_specs)}
        specs["layers"] = [{mixers[k][0]: mixers[k][1](), "ln": P(None)}
                           for k in cfg.layer_pattern]
    elif cfg.family == "ssm":
        specs["layers"] = stack({"mamba": mamba_specs(), "ln": P(None)})
    elif cfg.family == "hybrid":
        specs["layers"] = stack({"mamba": mamba_specs(), "ln": P(None)},
                                extra_lead=2)
        trailing = cfg.num_layers % cfg.attn_every
        if trailing:
            specs["trailing"] = stack({"mamba": mamba_specs(), "ln": P(None)})
        specs["shared_attn"] = {
            "attn": attn_specs(), "mlp": mlp_specs(),
            "ln1": P(None), "ln2": P(None),
        }
    else:
        layer = {
            "attn": attn_specs(),
            "mlp": moe_specs() if cfg.family == "moe" else mlp_specs(),
            "ln1": P(None), "ln2": P(None),
        }
        specs["layers"] = stack(layer)
    return specs


def batch_pspecs(cfg: ModelConfig, mesh, global_batch: int) -> Any:
    """Specs for {tokens, labels, frontend_*} train/prefill inputs."""
    dp = batch_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b_spec = dp if _div(global_batch, dp_size) else None
    out = {"tokens": P(b_spec, None), "labels": P(b_spec, None)}
    if cfg.frontend == "vision":
        out["frontend_embeds"] = P(b_spec, None, None)
        out["frontend_mask"] = P(b_spec, None)
    elif cfg.frontend == "audio":
        out["frontend_embeds"] = P(b_spec, None, None)
    return out


def cache_pspecs(cfg: ModelConfig, mesh, global_batch: int) -> Any:
    """Specs for the decode cache. Batch shards over (pod, data) when it
    divides; otherwise (long_500k, batch 1) the *sequence* dim shards over
    data (split-K decode) and SSM states replicate over data."""
    m = _model_size(mesh)
    dp = batch_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b_ok = _div(global_batch, dp_size)
    kv_ok = _div(cfg.num_kv_heads, m)
    # long-context (batch 1): cache seq shards over `data` (split-K decode).
    # Non-divisible KV heads: cache seq shards over `model` instead of
    # replicating a 32k-deep cache per chip (musicgen decode: 317 GB/dev
    # before this; EXPERIMENTS §Perf it.7).
    seq_axis = None if b_ok else "data"
    if b_ok and not kv_ok:
        seq_axis = "model"

    # leaves carry 1 or 2 leading stacking dims (layers / sites×layers)
    def attn_kv(lead):
        pre = [None] * lead
        return P(*pre, dp if b_ok else None, seq_axis,
                 "model" if kv_ok else None, None)

    def conv(lead):
        pre = [None] * lead
        return P(*pre, dp if b_ok else None, None, None)

    def state(lead):
        pre = [None] * lead
        return P(*pre, dp if b_ok else None, None, None, None)

    if cfg.layer_pattern:
        kinds = {"M": lambda: {"conv": conv(0), "state": state(0)},
                 "*": lambda: {"k": attn_kv(0), "v": attn_kv(0)},
                 "E": lambda: None}
        return {"layers": [kinds[k]() for k in cfg.layer_pattern]}
    if cfg.family == "ssm":
        return {"layers": {"conv": conv(1), "state": state(1)}}
    if cfg.family == "hybrid":
        out = {
            "layers": {"conv": conv(2), "state": state(2)},
            "shared": {"k": attn_kv(1), "v": attn_kv(1)},
        }
        if cfg.num_layers % cfg.attn_every:
            out["trailing"] = {"conv": conv(1), "state": state(1)}
        return out
    return {"layers": {"k": attn_kv(1), "v": attn_kv(1)}}
