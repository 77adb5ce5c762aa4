"""End-to-end driver: train a transformer LM with CL-SIA gradient
aggregation (the paper's best algorithm) as the data-parallel collective.

Default is a CPU-friendly ~3M-param model for a few hundred steps; pass
--params 100m for the full-size run (same code path — the 100M config
simply takes hours on CPU).

    PYTHONPATH=src python examples/train_lm_sia.py --steps 200
    PYTHONPATH=src python examples/train_lm_sia.py --params 100m --steps 300
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.data.synthetic import lm_batch, make_bigram_lm
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import OptConfig
from repro.train.state import TrainConfig
from repro.train.step import build_train_step, init_state, state_shardings

CONFIGS = {
    "3m": ModelConfig(name="lm-3m", family="dense", num_layers=4,
                      d_model=128, num_heads=4, num_kv_heads=2, d_ff=512,
                      vocab_size=512, head_dim=32, param_dtype="float32"),
    "100m": ModelConfig(name="lm-100m", family="dense", num_layers=12,
                        d_model=768, num_heads=12, num_kv_heads=4,
                        d_ff=3072, vocab_size=32000, head_dim=64,
                        param_dtype="float32"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--params", choices=list(CONFIGS), default="3m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--q-frac", type=float, default=0.01)
    args = ap.parse_args()

    cfg = CONFIGS[args.params]
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    tc = TrainConfig(agg=AggConfig(kind=AggKind.CL_SIA, q=1),
                     opt=OptConfig(name="adamw", lr=1e-3, grad_clip=1.0),
                     q_frac=args.q_frac, agg_dtype="float32",
                     ef_dtype="float32", lr_warmup=20)

    with jax.set_mesh(mesh):
        state = jax.device_put(
            init_state(cfg, tc, mesh, jax.random.PRNGKey(0)),
            state_shardings(cfg, tc, mesh))
        step = jax.jit(build_train_step(cfg, tc, mesh))
        lm = make_bigram_lm(jax.random.PRNGKey(7), cfg.vocab_size)
        key = jax.random.PRNGKey(1)
        t0 = time.time()
        for i in range(args.steps):
            key, kb = jax.random.split(key)
            state, m = step(state, lm_batch(lm, kb, args.batch, args.seq))
            if i % 20 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(m['loss']):.4f} "
                      f"uplink {float(m['agg_bits'])/8e6:.2f} MB "
                      f"({time.time()-t0:.0f}s)")
        # a bigram LM's optimal CE is well below the unigram entropy —
        # verify we actually learned structure
        print(f"final loss {float(m['loss']):.4f} "
              f"(uniform would be {float(jnp.log(cfg.vocab_size)):.2f})")


if __name__ == "__main__":
    main()
