"""Plain reference of one federated Nemotron-H train step on one client.

Written from the Nemotron-H layer equations (the configuration file's
``assumed.layer_equations``); it imports nothing of the program. Float32
with ``highest`` matrix precision; weights arrive in bfloat16 and are
widened. Each block is pre-norm residual, ``h = h + mixer(rmsnorm(h))``:

    M  [z, xBC, dt] = x · W_in;  xBC = silu(causal depthwise conv(xBC) + b)
       dt = softplus(dt + dt_bias);  A = -exp(A_log)
       y_t = Σ_{s≤t} (C_t·B_s)_g(h) · exp(Σ_{s<r≤t} dt_r A) · dt_s x_s + D x_t
       (head h reads group g(h) = h // (H/G))
       out = (rmsnorm per group of d_inner/G of y · silu(z)) · norm · W_out
    E  s = sigmoid(x · W_r) over every expert; the top k of s, weighted
       scale · s_i / Σ_top s; out = Σ_held w_e(x) relu(x·U_e)²·D_e
       + relu(x·U_s)²·D_s (the shared expert)
    *  causal GQA, scale 1/sqrt(head_dim), no bias, no rotary embedding

then a final norm and an untied head; the loss is the mean next-token
cross-entropy, padded vocabulary rows masked. The SSD sum and the attention
are written in their plain quadratic form, a block of query rows at a time
(``block`` rows, each block recomputed in the backward pass), one batch row
at a time. The experts are a loop over this chip's held ones, each applied
to every token and masked by the route; experts held elsewhere are left
out, as the program leaves them. ``routed_slots`` counts the (token, choice)
slots routed to a held expert.

The federated step runs on the host in float32 NumPy: the gradient is
stored in bfloat16, one CL-SIA hop keeps the q entries of largest |g + e|
(ties by lowest index), the rest is kept as error feedback in bfloat16, and
AdamW (with ``bench/refs/mamba2.py``'s warm-up and cosine learning-rate
scale) moves the float32 master.

``mm_dtype`` float8_e4m3fn is the lower-precision control: every matrix
product's inputs are rounded to it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.mamba2 import leaf_slices, lr_scale

HI = jax.lax.Precision.HIGHEST


def _mm(a, b, spec, mm_dtype):
    if mm_dtype is not None:
        info = jnp.finfo(mm_dtype)
        a, b = (jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                         mantissa_bits=info.nmant)
                for x in (a, b))
    return jnp.einsum(spec, a, b, precision=HI)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _query_blocks(fn, seq: int, block: int):
    """fn(first row) → [block, ...] over the query blocks, concatenated."""
    starts = jnp.arange(0, seq, block)
    out = jax.lax.map(jax.checkpoint(fn), starts)
    return out.reshape(seq, *out.shape[2:])


def mamba(p, x, dims, mm_dtype):
    """The M mixer on one row: x [L, D] (normed) → [L, D]."""
    di, g, n = dims["di"], dims["g"], dims["n"]
    hh, pp, blk = dims["h"], dims["p"], dims["block"]
    seq = x.shape[0]
    zxbcdt = _mm(x, p["in_proj"], "ld,de->le", mm_dtype)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * g * n]
    dt = zxbcdt[:, 2 * di + 2 * g * n:]
    k = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(xp[i:i + seq] * p["conv_w"][i] for i in range(k))
                      + p["conv_b"])
    xs = xbc[:, :di].reshape(seq, hh, pp)
    b = xbc[:, di:di + g * n].reshape(seq, g, n)
    c = xbc[:, di + g * n:].reshape(seq, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # [L, H]
    cum = jnp.cumsum(dt * -jnp.exp(p["a_log"]), axis=0)       # [L, H]

    def rows(t0):
        ct = jax.lax.dynamic_slice_in_dim(c, t0, blk)
        seg = (jax.lax.dynamic_slice_in_dim(cum, t0, blk)[:, None, :]
               - cum[None, :, :])                             # [t, s, H]
        causal = ((t0 + jnp.arange(blk))[:, None]
                  >= jnp.arange(seq)[None, :])[:, :, None]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        cb = _mm(ct, b, "tgn,sgn->tsg", mm_dtype)             # [t, s, G]
        att = jnp.repeat(cb, hh // g, axis=2) * decay * dt[None]
        return jnp.einsum("tsh,shp->thp", att, xs, precision=HI)

    y = _query_blocks(rows, seq, blk)
    y = y + xs * p["d_skip"][None, :, None]
    y = y.reshape(seq, di) * jax.nn.silu(z)
    y = _rmsnorm(y.reshape(seq, g, di // g), dims["eps"]).reshape(seq, di)
    return _mm(y * p["norm"], p["out_proj"], "le,ed->ld", mm_dtype)


def attention(p, x, dims, mm_dtype):
    """The * mixer on one row: causal GQA, no rotary embedding."""
    hq, hkv, dh, blk = dims["hq"], dims["hkv"], dims["dh"], dims["block"]
    seq = x.shape[0]
    q = _mm(x, p["wq"], "ld,de->le", mm_dtype).reshape(seq, hkv, hq // hkv,
                                                        dh)
    k = _mm(x, p["wk"], "ld,de->le", mm_dtype).reshape(seq, hkv, dh)
    v = _mm(x, p["wv"], "ld,de->le", mm_dtype).reshape(seq, hkv, dh)

    def rows(t0):
        qt = jax.lax.dynamic_slice_in_dim(q, t0, blk)
        s = _mm(qt, k, "tkgd,skd->kgts", mm_dtype) / math.sqrt(dh)
        causal = ((t0 + jnp.arange(blk))[:, None]
                  >= jnp.arange(seq)[None, :])
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgts,skd->tkgd", w, v, precision=HI)

    o = _query_blocks(rows, seq, blk).reshape(seq, hq * dh)
    return _mm(o, p["wo"], "le,ed->ld", mm_dtype)


def experts(p, x, dims, mm_dtype):
    """The E mixer on one row → ([L, D], slots routed to held experts)."""
    first, held = dims["first"], dims["held"]
    s = jax.nn.sigmoid(_mm(x, p["router"], "ld,de->le", mm_dtype))
    top, idx = jax.lax.top_k(s, dims["topk"])
    w = dims["scale"] * top / jnp.sum(top, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(held):
        we = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        hj = jnp.square(jax.nn.relu(_mm(x, p["w_up"][j], "ld,df->lf",
                                        mm_dtype)))
        y = y + we[:, None] * _mm(hj, p["w_down"][j], "lf,fd->ld", mm_dtype)
    hs = jnp.square(jax.nn.relu(_mm(x, p["shared_up"], "ld,df->lf",
                                    mm_dtype)))
    y = y + _mm(hs, p["shared_down"], "lf,fd->ld", mm_dtype)
    slots = jnp.sum((idx >= first) & (idx < first + held))
    return y, slots


MIXERS = {"M": ("mamba", mamba), "*": ("attn", attention),
          "E": ("moe", experts)}


def row_loss(params, tokens, labels, dims, mm_dtype):
    """Summed cross-entropy of one row (tokens, labels [L]) and the slots
    it routed to held experts."""
    f32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    h = f32["embed"][tokens]
    slots = jnp.int32(0)
    for kind, p in zip(dims["pattern"], f32["layers"]):
        name, mixer = MIXERS[kind]

        def block(hh, pl, kind=kind, name=name, mixer=mixer):
            out = mixer(pl[name], _rmsnorm(hh, dims["eps"]) * pl["ln"], dims,
                        mm_dtype)
            if kind == "E":
                return hh + out[0], out[1]
            return hh + out, jnp.int32(0)

        h, s = jax.checkpoint(block)(h, p)
        slots = slots + s
    h = _rmsnorm(h, dims["eps"]) * f32["final_norm"]
    logits = _mm(h, f32["lm_head"], "ld,dv->lv", mm_dtype)
    pad = jnp.arange(logits.shape[-1]) >= dims["vocab"]
    logits = jnp.where(pad[None, :], -1e30, logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return ce, slots


@functools.partial(jax.jit, static_argnames=("dims", "mm_dtype"))
def row_value_and_grad(params, tokens, labels, *, dims, mm_dtype):
    return jax.value_and_grad(row_loss, has_aux=True)(
        params, tokens, labels, dict(dims), mm_dtype)


def loss_and_grad(params, tokens, labels, *, dims: dict, mm_dtype=None):
    """Mean cross-entropy over the batch, its float32 gradient and the
    routed slots, one row at a time."""
    rows, seq = tokens.shape
    total, grad, slots = 0.0, None, 0
    key = tuple(sorted(dims.items()))
    for r in range(rows):
        (v, s), g = row_value_and_grad(params, tokens[r], labels[r],
                                       dims=key, mm_dtype=mm_dtype)
        total += float(v)
        slots += int(s)
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    n = rows * seq
    return total / n, jax.tree.map(lambda t: t / n, grad), slots


def host_flat(tree, dtype) -> np.ndarray:
    """Leaves in key order, raveled, cast on the device, concatenated on
    the host."""
    return np.concatenate([np.asarray(jnp.ravel(l).astype(dtype))
                           for l in jax.tree.leaves(tree)])


def host_params(master: np.ndarray, like):
    """The weights of a float32 host master in ``like``'s dtypes, on the
    device."""
    out = []
    for leaf, s in zip(jax.tree.leaves(like), leaf_slices(like).values()):
        out.append(jnp.asarray(master[s].astype(leaf.dtype)).reshape(
            leaf.shape))
    return jax.tree.unflatten(jax.tree.structure(like), out)


def cl_sia_one_client(g: np.ndarray, e: np.ndarray, q: int):
    """One CL-SIA hop, one client, exact Top-Q on the host: g, e bfloat16
    → (aggregate f32, e' bfloat16, nnz). Ties at the q-th magnitude go to
    the lowest indices."""
    gt = g.astype(np.float32) + e.astype(np.float32)
    mag = np.abs(gt)
    tau = np.partition(mag, mag.size - q)[mag.size - q]
    keep = mag > tau
    need = q - int(np.count_nonzero(keep))
    if need > 0:
        keep[np.flatnonzero(mag == tau)[:need]] = True
    del mag
    out = np.where(keep, gt, np.float32(0))
    e_new = np.where(keep, np.float32(0), gt).astype(jnp.bfloat16)
    return out, e_new, int(np.count_nonzero(out))


def adamw(master, m, v, g, t: int, lr: float, opt: dict) -> None:
    """One AdamW step on float32 host arrays, in place."""
    b1, b2 = np.float32(opt["b1"]), np.float32(opt["b2"])
    m *= b1
    m += (np.float32(1) - b1) * g
    v *= b2
    v += (np.float32(1) - b2) * np.square(g)
    upd = m / np.float32(1 - opt["b1"] ** t)
    upd /= np.sqrt(v / np.float32(1 - opt["b2"] ** t)) + np.float32(
        opt["eps"])
    upd += np.float32(opt["weight_decay"]) * master
    upd *= np.float32(lr)
    master -= upd


def train(params, batches, *, steps: int, dims: dict, q: int, opt: dict,
          mm_dtype=None) -> dict:
    """``steps`` federated steps from ``params`` (bfloat16, on the device)
    on ``batches`` (tokens, labels) → losses, bits and routed slots of
    each step; the first aggregate, the error feedback after the first
    step and the master's change, as host arrays."""
    master0 = host_flat(params, jnp.float32)
    master = master0.copy()
    m = np.zeros_like(master)
    v = np.zeros_like(master)
    e = np.zeros(master.shape, jnp.bfloat16)
    ib = max(1, math.ceil(math.log2(master.shape[0])))
    losses, bits, slots, first, ef1 = [], [], [], None, None
    live = np.zeros(0, np.int64)
    for s in range(steps):
        tokens, labels = batches[s]
        loss, grad, n_slots = loss_and_grad(params, tokens, labels,
                                            dims=dims, mm_dtype=mm_dtype)
        g = host_flat(grad, jnp.bfloat16)
        del grad
        agg, e, nnz = cl_sia_one_client(g, e, q)
        del g
        if first is None:
            first, ef1 = agg, e
        lr = opt["lr"] * lr_scale(s, opt["warmup"], opt["decay_steps"])
        if opt["weight_decay"]:
            adamw(master, m, v, agg, s + 1, lr, opt)
        else:
            # an entry no step has sent has m = v = g = 0: AdamW moves it
            # by 0, so the step runs on the entries some step sent
            live = np.union1d(live, np.flatnonzero(agg))
            sub = [a[live] for a in (master, m, v)]
            adamw(*sub, agg[live], s + 1, lr, opt)
            for a, b in zip((master, m, v), sub):
                a[live] = b
        params = host_params(master, params)
        losses.append(loss)
        bits.append(float(nnz) * (32 + ib))
        slots.append(n_slots)
    master -= master0
    return {"loss": losses, "bits": bits, "routed_slots": slots,
            "first": first, "ef1": ef1, "change": master}
