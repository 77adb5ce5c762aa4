"""Serve a small model with batched requests: prefill + decode loop,
reporting tokens/s and the shape of the KV-cache working set.

    PYTHONPATH=src python examples/serve_decode.py --arch mixtral-8x7b
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_mesh
from repro.models import model as model_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="mixtral-8x7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    max_len = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
        cache = model_mod.init_cache(cfg, args.batch, max_len)
        cache_bytes = sum(l.nbytes for l in jax.tree.leaves(cache))
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size)
        prefill = jax.jit(lambda p, t, c: model_mod.prefill(cfg, p, t, c))
        decode = jax.jit(
            lambda p, c, t, pos: model_mod.decode_step(cfg, p, c, t, pos))

        logits, cache = prefill(params, prompts, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = [tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            logits, cache = decode(params, cache, tok,
                                   jnp.int32(args.prompt_len + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(tok)
        jax.block_until_ready(tok)
        dt = time.time() - t0
        print(f"{cfg.name}: batch={args.batch}, KV cache "
              f"{cache_bytes/1e6:.1f} MB"
              + (f" (SWA ring buffer, window={cfg.sliding_window})"
                 if cfg.sliding_window else ""))
        print(f"decode: {args.batch*(args.gen-1)/dt:.1f} tok/s "
              f"({dt*1000/(args.gen-1):.1f} ms/step)")
        print("first request's tokens:",
              [int(t[0]) for t in toks[:12]], "...")


if __name__ == "__main__":
    main()
