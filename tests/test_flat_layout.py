"""FlatLayout: shard-aligned flatten/unflatten roundtrip (multi-device)."""

LAYOUT_ROUNDTRIP = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core.flat_layout import FlatLayout
from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.models import partition

mesh = compat.make_mesh((2, 4), ("data", "model"))
# num_heads=6 NOT divisible by model=4 → exercises the replicated-leaf path
cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                  num_heads=6, num_kv_heads=2, d_ff=64, vocab_size=128,
                  head_dim=8, param_dtype="float32")
params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
specs = partition.param_pspecs(cfg, mesh)
layout = FlatLayout(model_mod.param_specs(cfg), specs, mesh)
assert layout.n_local % layout.k_dp == 0

def roundtrip(p):
    m_idx = jax.lax.axis_index("model")
    col = layout.local_flatten(jax.tree.leaves(p), m_idx, jnp.float32)
    leaves = layout.local_unflatten(col, m_idx)
    return layout.treedef.unflatten(leaves)

f = compat.shard_map(roundtrip, mesh=mesh,
                     in_specs=(layout.param_in_specs(),),
                     out_specs=layout.param_out_specs(),
                     axis_names={"data", "model"})
with jax.set_mesh(mesh):
    out = jax.jit(f)(params)
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-6)
print("roundtrip OK; d_flat =", layout.d_flat)

# master init path agrees with a host-side flatten of the same layout
from repro.train.step import make_layout, _master_from_params
from repro.train.state import TrainConfig
master = _master_from_params(cfg, mesh, layout, params)
assert master.shape == (layout.d_flat,)
# total parameter mass preserved
tot_master = float(jnp.sum(jnp.abs(master)))
tot_params = float(sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                       for l in jax.tree.leaves(params)))
np.testing.assert_allclose(tot_master, tot_params, rtol=1e-5)
print("PASS")
"""


def test_flat_layout_roundtrip(multidev):
    multidev(LAYOUT_ROUNDTRIP, devices=8)
