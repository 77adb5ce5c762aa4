"""Layer scopes: the names the program gives the device work it issues.

Each layer wraps its work in a ``jax.named_scope`` named here. A scope is
metadata only: it lands in the ``op_name`` of every HLO instruction the
work lowers to (a fusion takes its root's), adds no operation and is
always on. A profiler trace names each device op by its HLO instruction,
so joining the trace with the compiled module's text
(``jax.jit(f).lower(...).compile().as_text()``) gives every op the layer
that issued it.

Scopes nest, and the innermost wins: a pad in ``cl_fuse_level``'s wrapper
runs under ``repro.executor/…/repro.node_step/…/repro.kernel_layout`` and
is ``kernel_layout``'s. A scope is matched as a path component anywhere in
the op_name, also inside a transform's parentheses, so the backward pass
``transpose(jvp(repro.grads))/…`` stays ``repro.grads``.

    python -c "from repro.obs import scopes; print(scopes.SCOPES)"
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

#: ``agg/plan.py`` executors: ext-row concatenations, the level scan's row
#: gathers and inbox scatter-adds, the final ``flat_pos`` reorder
EXECUTOR = "repro.executor"
#: ``core/algorithms.py`` node steps: sparsify+EF, IA combine, §V stats
NODE_STEP = "repro.node_step"
#: ``core/sparsify.py`` Top-Q thresholds: max pass, count passes, top-k, sort
TAU_SEARCH = "repro.tau_search"
#: ``kernels/level.py`` operand layout: pads to the kernels' blocks and back
KERNEL_LAYOUT = "repro.kernel_layout"
#: ``agg/device.py`` collectives: ppermute hops, butterflies, all-gathers
TRANSPORT = "repro.transport"
#: train step phase 1 and the simulator round's local gradients
GRADS = "repro.grads"
#: ``models/moe.py``'s dropless expert layer: router, dispatch, experts
MOE = "repro.moe"
#: train step phase 2 and the simulator round's aggregation
AGGREGATE = "repro.aggregate"
#: train step phase 3: the flat optimizer and the downlink
OPTIMIZER = "repro.optimizer"
#: the simulator round's logged loss
EVAL = "repro.eval"

SCOPES = (EXECUTOR, NODE_STEP, TAU_SEARCH, KERNEL_LAYOUT, TRANSPORT, GRADS,
          MOE, AGGREGATE, OPTIMIZER, EVAL)

_COMPONENT = re.compile(r"(?:^|[/(])(repro\.[A-Za-z_]+)(?=$|[/)])")


def scopes_in(op_name: str) -> tuple:
    """The known scopes of an op_name path, outermost first."""
    return tuple(s for s in _COMPONENT.findall(op_name) if s in SCOPES)


def innermost(op_name: str) -> Optional[str]:
    """The innermost known scope of an op_name path, or None."""
    found = scopes_in(op_name)
    return found[-1] if found else None


# ---------------------------------------------------------------------------
# The compiled module's text
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Instruction:
    name: str             # unique in its module, e.g. ``pad_reduce_fusion.12``
    shape: str            # the result's type, e.g. ``f32[57344]{0:T(1024)}``
    opcode: str
    operands: tuple       # the names of the instructions it reads
    op_name: str          # metadata op_name, "" where the compiler set none
    computation: str      # the computation that holds it
    executed: bool        # its computation runs as device ops (not fused)
    path: tuple           # its known scopes, outermost first, own or inherited
    inherited: bool       # ``path`` is a neighbour's: it has no op_name

    @property
    def scope(self) -> Optional[str]:
        """The innermost known scope, or None."""
        return self.path[-1] if self.path else None


_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
# computations that run as device ops of their own, not fused into another
_CONTROL = re.compile(r"\b(?:body|condition|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CALL = re.compile(r"\bto_apply=%?([\w.\-]+)")


def _operands(rest: str, start: int) -> list:
    """The ``%names`` between the opcode's parenthesis and its match."""
    depth = 0
    for end in range(start, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[end], 0)
        if depth == 0:
            return _OPERAND.findall(rest[start:end])
    return []


def instructions(hlo_text: str) -> list:
    """Every instruction of an HLO module's text, with its scope.

    An instruction the compiler made (a copy of a loop carry, a broadcast,
    a scatter's bounds check) has no op_name; it takes the scopes of the
    nearest instruction it feeds that has one (a computation's root feeds
    the control-flow instruction that runs the computation), else, where it
    feeds only the module's result, of the nearest one it reads, and is
    marked ``inherited``: the program did not scope it, so a reader keeps
    its time apart from the time the program's own scopes cover."""
    rows, calls, roots, entry, comp = [], {}, {}, None, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line:
            comp = m.group(2)
            calls.setdefault(comp, set())
            if m.group(1):
                entry = comp
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name, rest = m.group(2), line[m.end():]
        op = _OPCODE.search(" " + rest)
        opcode = op.group(1) if op else ""
        shape = rest[:op.start() - 1].strip() if op else ""
        operands = _operands(rest, op.end() - 2) if op else []
        meta = _OP_NAME.search(rest)
        rows.append((name, shape, opcode, meta.group(1) if meta else "",
                     comp, operands))
        if m.group(1):
            roots[comp] = name
        callees = set(_CONTROL.findall(rest))
        for br in _BRANCHES.findall(rest):
            callees.update(b.strip().lstrip("%") for b in br.split(","))
        if opcode == "call":
            callees.update(_CALL.findall(rest))
        calls[comp] |= {(c, name) for c in callees}
    run, todo = set(), [entry] if entry else []
    users: dict = {}
    for name, *_, operands in rows:
        for o in operands:
            users.setdefault(o, []).append(name)
    for edges in calls.values():
        for callee, caller in edges:
            if callee in roots:
                users.setdefault(roots[callee], []).append(caller)
    while todo:
        c = todo.pop()
        if c not in run:
            run.add(c)
            todo.extend(callee for callee, _ in calls.get(c, ()))
    op_name = {r[0]: r[3] for r in rows}
    operands = {r[0]: r[5] for r in rows}

    def nearest(name: str, edges: dict) -> Optional[tuple]:
        seen, queue = {name}, [name]
        while queue:
            n = queue.pop(0)
            if op_name.get(n):
                return scopes_in(op_name[n])
            for u in edges.get(n, ()):
                if u not in seen:
                    seen.add(u)
                    queue.append(u)
        return None

    def resolve(name: str) -> tuple:
        found = nearest(name, users)
        if found is None:           # feeds only the module's result
            found = nearest(name, operands)
        return found or ()

    return [Instruction(n, sh, o, tuple(ops), p, c, c in run, resolve(n),
                        not p)
            for n, sh, o, p, c, ops in rows]

