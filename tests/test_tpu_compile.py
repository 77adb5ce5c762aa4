"""Ahead-of-time compiles for a described TPU v5e chip.

Interpret-mode parity cannot see what the TPU compiler refuses: blocks not
aligned to the (8, 128) tiling, scalars stored to vector memory, more fast
memory than a kernel may hold. These tests compile the Pallas kernels at
the paper's width (d=7850) and at a model-scale segment (d=2^24), and one
whole aggregation round with the kernels on, for a chip that is described
and not attached. Nothing runs. Each compiled program must hold the Mosaic
kernel (``tpu_custom_call``).

The topology is described inside a module fixture — never at import, in a
``parametrize`` argument or in ``conftest.py`` — and the fixture skips
where it cannot be described. JAX's persistent compile cache is off while
these tests run: a compile for a described chip cannot be read back.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.agg import compile_plan, execute
from repro.core.algorithms import AggConfig, AggKind
from repro.kernels import level
from repro.obs import scopes
from repro.topo.graph import walker_delta


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as exc:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_calls(w: int, d: int, branch: int = 64):
    """(name, fn, shapes) for every kernel of repro.kernels.level, in the
    operand forms the fused node steps use."""
    v, s, gm = (w, d), (w,), (d,)
    tables = [(w, branch)] + [(w, branch + 1)] * 3
    return {
        "sparsify_ef": (functools.partial(
            level.sparsify_ef_level_pallas, with_err=True),
            [v, v, v, s, s, s]),
        "sparsify_ef_nomask": (
            lambda g, e, wt, t, va: level.sparsify_ef_level_pallas(
                g, e, None, wt, t, va),
            [v, v, s, s, s]),
        "chain_accum": (level.chain_accum_level_pallas, [v, v, s, gm]),
        "cl_fuse": (functools.partial(
            level.cl_fuse_level_pallas, with_err=True),
            [v, v, v, s, s, s, s, gm, v]),
        "count_ge": (level.count_ge_level_pallas, [v, (w, branch)]),
        "count_ge_fused": (functools.partial(
            level.count_ge_fused_level_pallas, include_gamma=True),
            [v, v, v, s, s, (w, branch), gm]),
        "hist_topq": (
            lambda g, e, gi, wt, p, t1, nl, w2, ts, m:
            level.hist_topq_level_pallas(g, e, gi, wt, p, (t1, nl, w2, ts),
                                         m, include_gamma=True),
            [v, v, v, s, s, *tables, gm]),
    }


KERNELS = list(_kernel_calls(1, 1))


@pytest.mark.parametrize("d", [7850, 1 << 24])
@pytest.mark.parametrize("w", [1, 8])
@pytest.mark.parametrize("kernel", KERNELS)
def test_level_kernel_compiles_for_v5e(one_chip, kernel, w, d):
    fn, shapes = _kernel_calls(w, d)[kernel]
    args = [jax.ShapeDtypeStruct(sh, jnp.float32, sharding=one_chip)
            for sh in shapes]
    assert "tpu_custom_call" in _compile(fn, *args)


ROUND_CELLS = [(kind, "threshold", "scan") for kind in (
    AggKind.SIA, AggKind.RE_SIA, AggKind.CL_SIA, AggKind.TC_SIA,
    AggKind.CL_TC_SIA)] + [(AggKind.CL_SIA, "exact", "scan"),
                           (AggKind.CL_TC_SIA, "threshold", "hist")]


@pytest.mark.parametrize("kind,topq,tau", ROUND_CELLS,
                         ids=lambda x: getattr(x, "value", x))
def test_execute_round_compiles_with_kernels(one_chip, monkeypatch, kind,
                                             topq, tau):
    """One whole round (K=8, d=2^20, routed Walker-delta tree) with the
    fused kernel path on. Off-TPU the dispatch resolves to the jnp
    reference, so the test steers it to compiled Pallas here."""
    import repro.kernels.ops as kops
    monkeypatch.setattr(kops, "resolve", lambda mode: (True, False))
    k, d = 8, 1 << 20
    cfg = AggConfig(kind=kind, q=d // 100, topq_impl=topq, tau_impl=tau,
                    hist_rounds=2 if tau == "hist" else 3)
    plan = compile_plan(walker_delta(2, 4))
    tc = kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA)

    def round_(g, e, w, gm):
        return execute(cfg, plan, g, e, w, global_mask=gm if tc else None)

    spec = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                             sharding=one_chip)
    text = _compile(round_, spec((k, d)), spec((k, d)), spec((k,)),
                    spec((d,)))
    assert "tpu_custom_call" in text


def test_unfused_round_has_no_kernels(one_chip):
    """The "never" dispatch compiles to plain XLA — what the fused path is
    compared against on the chip."""
    k, d = 8, 1 << 20
    cfg = dataclasses.replace(AggConfig(kind=AggKind.CL_SIA, q=d // 100),
                              kernel_mode="never")
    plan = compile_plan(k)
    spec = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                             sharding=one_chip)
    text = _compile(lambda g, e, w: execute(cfg, plan, g, e, w),
                    spec((k, d)), spec((k, d)), spec((k,)))
    assert "tpu_custom_call" not in text


# ---------------------------------------------------------------------------
# Layer scopes of the fused round (repro.obs.scopes)
# ---------------------------------------------------------------------------

SCOPED_D = 50_000       # not a whole number of kernel blocks: the operands pad
# the round cell's shape: one chip's K = 8 chain over 16,132,152 entries
CELL_K, CELL_D = 8, 16_132_152


def _round_text(chip, k: int, d: int) -> str:
    """The compiled text of a K-client CL-SIA threshold chain round with
    the kernels on."""
    import repro.kernels.ops as kops
    cfg = AggConfig(kind=AggKind.CL_SIA, q=d // 100, topq_impl="threshold")
    spec = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                             sharding=chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "resolve", lambda mode: (True, False))
        return _compile(
            lambda g, e, w, p: execute(cfg, compile_plan(k), g, e, w,
                                       participate=p),
            spec((k, d)), spec((k, d)), spec((k,)), spec((k,)))


@pytest.fixture(scope="module")
def scoped_round_text(one_chip):
    return _round_text(one_chip, 4, SCOPED_D)


@pytest.fixture(scope="module")
def scoped_round(scoped_round_text):
    """The working instructions of a K=4 CL-SIA threshold chain round with
    the kernels on, each with its scope."""
    return [i for i in scopes.instructions(scoped_round_text)
            if i.executed and i.opcode != "parameter"]


@pytest.mark.parametrize("kernel,scope", [
    ("cl_fuse_level_pallas", scopes.NODE_STEP),
    ("count_ge_fused_level_pallas", scopes.TAU_SEARCH),
])
def test_kernels_keep_their_names_in_their_layer(scoped_round, kernel,
                                                 scope):
    calls = [i for i in scoped_round if i.name.rsplit(".", 1)[0] == kernel]
    assert calls and all(i.opcode == "custom-call" for i in calls)
    assert all(i.scope == scope and not i.inherited for i in calls)


def _entries(shape: str) -> int:
    """float32 entries of an HLO result type (a tuple's summed): the
    round's data, not its integer counters."""
    return sum(int(np.prod([int(n) for n in dims.split(",") if n]))
               for dims in re.findall(r"f32\[([\d,]*)\]", shape))


_RELAYOUT = ("pad", "slice", "reshape", "copy")


def _is_relayout(i) -> bool:
    """A pad, slice, reshape or layout copy, alone or at a fusion's head."""
    head = i.name.split("_", 1)[0] if i.opcode == "fusion" else i.opcode
    return head in _RELAYOUT


def _assert_round_layout(text: str, d: int):
    """The round is carried in the kernels' block layout: d-sized data is
    padded into blocks and cut back only at the round's entry and exit,
    under ``repro.kernel_layout``, and no one-row tile feeds a kernel."""
    every = scopes.instructions(text)
    shape = {i.name: i.shape for i in every}
    entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M).group(1)
    run = [i for i in every if i.executed and i.opcode != "parameter"]

    def wide(i) -> bool:      # reads or writes d or more entries
        return max(_entries(shape.get(n, "")) for n in
                   (i.name,) + i.operands) >= d

    # inside the level loop (every computation the entry runs): no
    # kernel_layout op and no pad, slice, reshape or relayout copy of
    # d-sized data; the row takes are dynamic slices of dense blocks
    loop = [i.name for i in run if i.computation != entry and wide(i)
            and (i.scope == scopes.KERNEL_LAYOUT or _is_relayout(i))]
    assert not loop, loop
    # at entry and exit: the two pads (g, e) and the two unpads (e', the
    # aggregate) are the program's own kernel_layout ops, and the layout
    # copies between them inherit that scope
    edges = [i for i in run if i.computation == entry and wide(i)
             and _is_relayout(i)]
    own = [i for i in edges if i.opcode in ("pad", "slice")]
    assert sorted(i.opcode for i in own) == ["pad", "pad", "slice", "slice"]
    assert all(not i.inherited for i in own)
    assert all(i.scope == scopes.KERNEL_LAYOUT for i in edges), \
        [(i.name, i.scope) for i in edges]
    # each kernel reads its d-sized operands as [W, n_blocks, 8, 1024]
    # blocks, never as a [1, d] row in one-row tiles
    calls = [i for i in run if i.opcode == "custom-call"
             and "_level_pallas" in i.name]
    assert calls
    for i in calls:
        rows = [shape[n] for n in i.operands if _entries(shape[n]) >= d]
        assert rows and all(re.match(r"f32\[\d+,\d+,8,1024\]", r)
                            and "T(1,128)" not in r for r in rows), rows
    assert not any(i.scope is None for i in run)


def test_operand_pads_are_kernel_layout(scoped_round_text):
    assert level._geometry(SCOPED_D)[1] > 0
    _assert_round_layout(scoped_round_text, SCOPED_D)


def test_operand_pads_are_kernel_layout_at_cell_width(one_chip):
    assert level._geometry(CELL_D)[1] > 0
    _assert_round_layout(_round_text(one_chip, CELL_K, CELL_D), CELL_D)


@pytest.mark.parametrize("w", [1, 8])
def test_exact_select_compiles_for_v5e(one_chip, w):
    """The train cell's exact Top-Q (1 % of all 129,057,216 entries) and a
    W = 8 level at 2^24 compile to counting passes: no sort, no top-k and
    no scatter, and a single lane is counted as a flat vector."""
    from repro.core import sparsify as sp
    d = 129_057_216 if w == 1 else 1 << 24
    x = jax.ShapeDtypeStruct((w, d), jnp.float32, sharding=one_chip)
    text = _compile(lambda v: sp.topq_mask(v, d // 100), x)
    assert "while" in text
    for op in (" sort(", "TopK", "top_k", " scatter("):
        assert op not in text, op
    if w == 1:      # the keys are counted in the flat [d] tiling
        assert f"u32[{d}]{{0:T(1024)}}" in text
