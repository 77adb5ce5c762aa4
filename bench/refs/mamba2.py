"""Plain reference of one federated Mamba2 train step on one client.

Written from the Mamba2 paper (arXiv:2405.21060) and the configuration's
stated layer equations; it imports nothing of the program. Float32 with
``highest`` matrix precision; weights arrive in the configuration's bfloat16
and are widened. Per layer (pre-norm residual):

    x = rmsnorm(h) · ln
    [z, xBC, dt] = x · W_in                     (xBC = [x, B, C])
    xBC = silu(causal depthwise conv(xBC))
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y_t = Σ_{s≤t} (C_t·B_s) · exp(Σ_{s<r≤t} dt_r A) · dt_s x_s + D x_t
    h = h + (rmsnorm(y · silu(z)) · norm) · W_out

The SSD sum is written in its plain quadratic form over the whole sequence,
one batch row at a time. The loss is the mean next-token cross-entropy with
the tied embedding as head, padded vocabulary rows masked.

The federated step: the gradient is stored in bfloat16 (the configuration's
G dtype), one CL-SIA hop keeps the q entries of largest |g + e| (ties by
lowest index), the rest is kept as error feedback in bfloat16, and AdamW
(with the warm-up and cosine learning-rate scale) moves the float32 master.

``mm_dtype`` float8_e4m3fn is the lower-precision control: every matrix
product's inputs are rounded to it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _mm(a, b, spec, mm_dtype):
    if mm_dtype is not None:
        # reduce_precision, not a round trip through the type: XLA may drop
        # a float32 → narrow → float32 pair as excess precision
        info = jnp.finfo(mm_dtype)
        a, b = (jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                         mantissa_bits=info.nmant)
                for x in (a, b))
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def layer(p, h, dims, eps, mm_dtype):
    """One Mamba2 layer on one row: h [L, D] → [L, D]."""
    di, n, hh, pp = dims["di"], dims["n"], dims["h"], dims["p"]
    seq = h.shape[0]
    x = _rmsnorm(h, p["ln"], eps)
    p = p["mamba"]
    zxbcdt = _mm(x, p["in_proj"], "ld,de->le", mm_dtype)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * n]
    dt = zxbcdt[:, 2 * di + 2 * n:]
    k = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(xp[i:i + seq] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(seq, hh, pp)
    b = xbc[:, di:di + n]
    c = xbc[:, di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # [L, H]
    a = -jnp.exp(p["a_log"])                                 # [H]
    cum = jnp.cumsum(dt * a, axis=0)                         # [L, H]
    seg = cum[:, None, :] - cum[None, :, :]                  # [t, s, H]
    causal = jnp.tril(jnp.ones((seq, seq), bool))[:, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = _mm(c, b, "tn,sn->ts", mm_dtype)                    # [t, s]
    att = cb[:, :, None] * decay * dt[None, :, :]            # [t, s, H]
    y = jnp.einsum("tsh,shp->thp", att, xs,
                   precision=jax.lax.Precision.HIGHEST)
    y = y + xs * p["d_skip"][None, :, None]
    y = y.reshape(seq, di) * jax.nn.silu(z)
    y = _rmsnorm(y, p["norm"], eps)
    return h + _mm(y, p["out_proj"], "le,ed->ld", mm_dtype)


def row_loss(params, tokens, labels, dims, vocab, eps, mm_dtype):
    """Summed cross-entropy of one row (tokens, labels [L])."""
    f32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    h = f32["embed"][tokens]
    body = jax.checkpoint(
        lambda hh, p: (layer(p, hh, dims, eps, mm_dtype), None))
    h, _ = jax.lax.scan(body, h, f32["layers"])
    h = _rmsnorm(h, f32["final_norm"], eps)
    logits = _mm(h, f32["embed"], "ld,vd->lv", mm_dtype)
    pad = jnp.arange(logits.shape[-1]) >= vocab
    logits = jnp.where(pad[None, :], -1e30, logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("dims", "vocab", "eps",
                                             "mm_dtype"))
def row_value_and_grad(params, tokens, labels, *, dims, vocab, eps,
                       mm_dtype):
    return jax.value_and_grad(row_loss)(params, tokens, labels,
                                        dict(dims), vocab, eps, mm_dtype)


def loss_and_grad(params, tokens, labels, *, dims, vocab, eps,
                  mm_dtype=None):
    """Mean cross-entropy over the batch and its float32 gradient, one row
    at a time."""
    rows, seq = tokens.shape
    total, grad = 0.0, None
    key = tuple(sorted(dims.items()))
    for r in range(rows):
        v, g = row_value_and_grad(params, tokens[r], labels[r], dims=key,
                                  vocab=vocab, eps=eps, mm_dtype=mm_dtype)
        total += float(v)
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    n = rows * seq
    return total / n, jax.tree.map(lambda t: t / n, grad)


def flatten(tree):
    """Leaves in key order (dicts sorted), raveled and concatenated (f32)."""
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                            for l in jax.tree.leaves(tree)])


def leaf_slices(tree) -> dict:
    out, off = {}, 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        n = int(np.prod(leaf.shape))
        out[jax.tree_util.keystr(path)] = slice(off, off + n)
        off += n
    return out


def unflatten(flat, like):
    leaves, off = [], 0
    for l in jax.tree.leaves(like):
        n = int(np.prod(l.shape))
        leaves.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(jax.tree.structure(like), leaves)


@functools.partial(jax.jit, static_argnames=("q",))
def cl_sia_one_client(g_bf16, e_bf16, *, q: int):
    """One CL-SIA hop, one client, exact Top-Q: → (aggregate f32, e' bf16,
    nnz)."""
    gt = g_bf16.astype(jnp.float32) + e_bf16.astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(gt), q)
    keep = jnp.zeros(gt.shape, bool).at[idx].set(True)
    out = jnp.where(keep, gt, 0.0)
    return out, jnp.where(keep, 0.0, gt).astype(jnp.bfloat16), \
        jnp.sum(out != 0)


def lr_scale(step: int, warmup: int, decay_steps: int,
             min_ratio: float = 0.1) -> float:
    warm = min(1.0, (step + 1) / max(warmup, 1))
    frac = min(max((step - warmup) / max(decay_steps - warmup, 1), 0.0), 1.0)
    return warm * (min_ratio + (1 - min_ratio) * 0.5 * (1 + math.cos(
        math.pi * frac)))


@jax.jit
def adamw(master, m, v, g, t, lr, b1, b2, eps, wd):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return master - lr * (mh / (jnp.sqrt(vh) + eps) + wd * master), m, v


def train(params, batches, *, steps: int, dims: dict, vocab: int, eps: float,
          q: int, opt: dict, mm_dtype=None) -> dict:
    """``steps`` federated steps from ``params`` on ``batches`` (tokens,
    labels) → losses, bits, the first aggregate, the error feedback after
    the first step and the master's change."""
    master0 = flatten(params)
    master = master0
    m = jnp.zeros_like(master)
    v = jnp.zeros_like(master)
    e = jnp.zeros(master.shape, jnp.bfloat16)
    ib = max(1, math.ceil(math.log2(master.shape[0])))
    losses, bits, first = [], [], None
    for s in range(steps):
        tokens, labels = batches[s]
        loss, grad = loss_and_grad(params, tokens, labels, dims=dims,
                                   vocab=vocab, eps=eps, mm_dtype=mm_dtype)
        agg, e, nnz = cl_sia_one_client(flatten(grad).astype(jnp.bfloat16),
                                        e, q=q)
        if first is None:
            first, ef1 = agg, e
        lr = opt["lr"] * lr_scale(s, opt["warmup"], opt["decay_steps"])
        master, m, v = adamw(master, m, v, agg, float(s + 1), lr, opt["b1"],
                             opt["b2"], opt["eps"], opt["weight_decay"])
        params = unflatten(master, params)
        losses.append(loss)
        bits.append(float(nnz) * (32 + ib))
    return {"loss": losses, "bits": bits, "first": first, "ef1": ef1,
            "change": master - master0}
