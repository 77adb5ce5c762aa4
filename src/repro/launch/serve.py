"""Serving CLI: batched prefill + decode loop with a reduced config.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --smoke --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_mesh
from repro.models import model as model_mod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="mamba2-130m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    max_len = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        params = model_mod.init_params(cfg, jax.random.PRNGKey(args.seed))
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size)
        cache = model_mod.init_cache(cfg, args.batch, max_len)

        prefill = jax.jit(
            lambda p, t, c: model_mod.prefill(cfg, p, t, c))
        decode = jax.jit(
            lambda p, c, t, pos: model_mod.decode_step(cfg, p, c, t, pos))

        t0 = time.time()
        logits, cache = prefill(params, prompts, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out = [tok]
        for i in range(args.gen - 1):
            logits, cache = decode(params, cache, tok,
                                   jnp.int32(args.prompt_len + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(tok)
        gen = jnp.stack(out, axis=1)
        dt = time.time() - t0
        print(f"arch={cfg.name} batch={args.batch} "
              f"prompt={args.prompt_len} generated={gen.shape[1]} tokens "
              f"in {dt:.2f}s ({args.batch*gen.shape[1]/dt:.1f} tok/s)")
        print("sample generations (token ids):")
        for row in list(gen[:2]):
            print("  ", list(map(int, row)))


if __name__ == "__main__":
    main()
