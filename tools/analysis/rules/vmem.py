"""vmem-budget: every ``pl.pallas_call`` site must provably fit VMEM.

The bug class: a kernel whose per-step buffers are sized from
data-dependent extents compiles fine on small inputs and OOMs the
compiler/chip at scale — the ~205K-merged-lane XLA cliff, and
the measured [32, 16384] f32 block that blew the 16M scoped-VMEM cap
at 23.5M.  The dynamic twin of this check is ``packing.asof_chunk_plan``
/ ``pallas_kernels._plan``; this rule is the static one, run at lint
time over every call site:

* Block shapes (BlockSpec), ``out_shape`` dtypes, and
  ``scratch_shapes`` are folded to constants where the source allows.
  A fully resolved site whose worst-case per-step bytes — VMEM-blocked
  inputs and outputs double-buffered (Mosaic pipelines I/O: the
  implicit 2x multi-buffering), scratch at its FULL declared shape —
  exceed the budget (``vmem_limit_bytes`` from ``compiler_params``
  when given, else the 16 MiB scoped default) is a violation outright.
  An explicit N-deep DMA ring (ops/pallas_stream.py) declares its
  buffering as the ring scratch's leading dim, so the N-fold cost is
  counted through the same shape folding; its ``memory_space=ANY``
  operands stay in HBM and count ZERO VMEM (the ring scratch IS their
  on-chip footprint), and DMA semaphores live in semaphore memory.
* A site with *unresolvable* extents (runtime ``K``/``L``) must sit in
  a function that consults a chunking/feasibility planner (a call
  whose name mentions plan/feasible/supported/chunk — ``_plan``,
  ``_plan_merge``, ``merge_join_supported``, ``asof_chunk_plan``
  ...); otherwise nothing bounds the bytes and the site is flagged.

The model counts *declared* buffers only — Mosaic's own network
temporaries are the planner's job (its ``arrays``/plane multipliers);
a static rule that guessed them would bless or damn sites on fiction.
Suppress a site whose guard lives in its callers with
``# lint-ok: vmem-budget: <where the plan is>``.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Any, List, Optional

from tools.analysis.core import ModuleSource, Rule, Violation
from tools.analysis import dataflow as df
from tools.analysis.dataflow import UNKNOWN

DEFAULT_BUDGET = 16 * 2**20  # Mosaic's default scoped-VMEM cap

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "float16": 2, "bfloat16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
}

#: a call counts as a chunking/feasibility guard when one of its
#: name's underscore-separated segments IS one of these tokens
#: (substring matching blessed 'explain'/'log_chunks'-style names).
_GUARD_HINTS = ("plan", "plans", "feasible", "supported", "chunk")


class _Spec:
    """One resolved BlockSpec: byte size per block, or UNKNOWN."""

    def __init__(self, bytes_per_block: Any, memory_space: str):
        self.bytes_per_block = bytes_per_block
        self.memory_space = memory_space


def _dtype_bytes(node: Optional[ast.expr]) -> Any:
    """jnp.float32 / np.int8 / 'float32' -> element size."""
    if node is None:
        return 4  # operand dtypes are invisible statically; assume word
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return _DTYPE_BYTES.get(node.value, UNKNOWN)
    name = df.terminal_name(node)
    return _DTYPE_BYTES.get(name, UNKNOWN)


def _shape_bytes(shape: Any, elem: Any) -> Any:
    if shape is UNKNOWN or elem is UNKNOWN:
        return UNKNOWN
    if not isinstance(shape, tuple):
        return UNKNOWN
    total = elem
    for dim in shape:
        if not isinstance(dim, int):
            return UNKNOWN
        total *= dim
    return total


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


class VmemBudgetRule(Rule):
    name = "vmem-budget"
    code = 1
    doc = ("pallas_call sites must statically fit the VMEM budget or "
           "sit behind a chunking/feasibility planner")

    def applies(self, path: Path) -> bool:
        return path.suffix == ".py"

    def check(self, mod: ModuleSource) -> List[Violation]:
        if "pallas_call" not in mod.text:
            return []
        tree = mod.tree
        module_env = df.assignment_env(tree.body)
        func_of = df.enclosing_function_map(tree)
        out: List[Violation] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and df.terminal_name(node.func) == "pallas_call"):
                continue
            enclosing = func_of.get(node)
            scope = (df.assignment_env(enclosing.body)
                     if enclosing is not None else module_env)
            fallback = module_env if enclosing is not None else None
            v = self._check_site(mod, node, enclosing, scope, fallback)
            if v is not None:
                out.append(v)
        return out

    # -- site analysis -------------------------------------------------

    def _check_site(self, mod, call, enclosing, env, fallback):
        budget = self._budget(call, env, fallback)
        total = 0
        # an unfoldable vmem_limit_bytes means the real cap is unknown:
        # the site must be guarded like any other unresolvable extent
        unresolved = budget is UNKNOWN

        for kw_name, pipelined in (("in_specs", True), ("out_specs", True)):
            specs = self._spec_list(_kw(call, kw_name), env, fallback)
            if specs is UNKNOWN:
                unresolved = True
                continue
            for spec in specs:
                if spec.memory_space in ("SMEM", "ANY"):
                    # scalar prefetch lives outside VMEM; ANY operands
                    # stay in HBM — a manual-DMA kernel's on-chip bytes
                    # are its declared ring/stage scratch
                    continue
                if spec.bytes_per_block is UNKNOWN:
                    unresolved = True
                else:
                    total += spec.bytes_per_block * (2 if pipelined else 1)

        scratch = self._scratch_bytes(_kw(call, "scratch_shapes"),
                                      env, fallback)
        if scratch is UNKNOWN:
            unresolved = True
        else:
            total += scratch

        if not unresolved and isinstance(budget, int) and total > budget:
            return self.violation(
                mod, call.lineno,
                f"pallas_call declares ~{total} bytes of per-step VMEM "
                f"(I/O blocks double-buffered + scratch) against a "
                f"{budget}-byte budget — shrink the blocks or grid over "
                f"the long axis (cf. packing.asof_chunk_plan / "
                f"pallas_kernels._plan)")
        if unresolved and not self._guarded(enclosing, mod):
            return self.violation(
                mod, call.lineno,
                "pallas_call block/scratch extents are not statically "
                "resolvable and no chunking guard (a *plan*/*feasible*/"
                "*supported* planner call) bounds them in the enclosing "
                "function — unbounded shapes re-create the ~205K-lane "
                "compiler-OOM class; add a VMEM plan or suppress with "
                "'# lint-ok: vmem-budget: <where the plan lives>'")
        return None

    def _budget(self, call, env, fallback) -> Any:
        cp = _kw(call, "compiler_params")
        if isinstance(cp, ast.Name):
            # params object built a few lines up: follow the assignment
            for scope in (env, fallback or {}):
                if cp.id in scope:
                    cp = scope[cp.id]
                    break
        if cp is None:
            return DEFAULT_BUDGET
        if isinstance(cp, ast.Call):
            limit = _kw(cp, "vmem_limit_bytes")
            if limit is not None:
                folded = df.fold(limit, env, fallback)
                return folded if isinstance(folded, int) else UNKNOWN
            return DEFAULT_BUDGET
        # unrecognized params expression: the raised-cap case cannot be
        # ruled out, nor confirmed — treat as unknown (guard required)
        return UNKNOWN

    def _spec_list(self, node, env, fallback) -> Any:
        """Resolve an in_specs/out_specs expression to a list of
        _Spec, or UNKNOWN.  Handles literals, ``[spec] * n``,
        list concatenation, and names bound to either."""
        if node is None:
            # defaulted specs block over the whole operand — sized by
            # runtime shapes, so statically unbounded
            return UNKNOWN
        if isinstance(node, ast.Name):
            for scope in (env, fallback or {}):
                if node.id in scope:
                    return self._spec_list(scope[node.id], env, fallback)
            return UNKNOWN
        if isinstance(node, (ast.List, ast.Tuple)):
            specs = []
            for elt in node.elts:
                sub = self._spec_list(elt, env, fallback)
                if sub is UNKNOWN:
                    return UNKNOWN
                specs.extend(sub)
            return specs
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
            for seq, count in ((node.left, node.right),
                               (node.right, node.left)):
                sub = self._spec_list(seq, env, fallback)
                if sub is UNKNOWN:
                    continue
                n = df.fold(count, env, fallback)
                if isinstance(n, int):
                    return sub * n
            return UNKNOWN
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            lhs = self._spec_list(node.left, env, fallback)
            rhs = self._spec_list(node.right, env, fallback)
            if lhs is UNKNOWN or rhs is UNKNOWN:
                return UNKNOWN
            return lhs + rhs
        if isinstance(node, ast.Call):
            name = df.terminal_name(node.func)
            if name == "BlockSpec":
                return [self._block_spec(node, env, fallback)]
        return UNKNOWN

    def _block_spec(self, call: ast.Call, env, fallback) -> "_Spec":
        space = "VMEM"
        ms = _kw(call, "memory_space")
        if ms is not None:
            space = df.terminal_name(ms) or "VMEM"
        shape_node = call.args[0] if call.args else _kw(call, "block_shape")
        if shape_node is None:
            # whole-operand block: sized by the runtime operand (0 for
            # the non-VMEM spaces — SMEM scalars, HBM-resident ANY)
            return _Spec(0 if space in ("SMEM", "ANY") else UNKNOWN,
                         space)
        shape = df.fold(shape_node, env, fallback)
        return _Spec(_shape_bytes(shape, 4), space)

    def _scratch_bytes(self, node, env, fallback) -> Any:
        if node is None:
            return 0
        if isinstance(node, ast.Name):
            for scope in (env, fallback or {}):
                if node.id in scope:
                    return self._scratch_bytes(scope[node.id], env, fallback)
            return UNKNOWN
        if isinstance(node, (ast.List, ast.Tuple)):
            total = 0
            for elt in node.elts:
                sub = self._scratch_bytes(elt, env, fallback)
                if sub is UNKNOWN:
                    return UNKNOWN
                total += sub
            return total
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            lhs = self._scratch_bytes(node.left, env, fallback)
            rhs = self._scratch_bytes(node.right, env, fallback)
            if lhs is UNKNOWN or rhs is UNKNOWN:
                return UNKNOWN
            return lhs + rhs
        if isinstance(node, ast.Call):
            name = df.terminal_name(node.func)
            if name in ("SMEM", "SemaphoreType", "DMA", "REGULAR",
                        "BARRIER"):
                # SMEM scalars and semaphores (pltpu.SemaphoreType.DMA
                # calls resolve to their rightmost attr) are not VMEM
                return 0
            if name == "VMEM":
                shape = df.fold(call_arg(node, 0), env, fallback)
                elem = _dtype_bytes(call_arg(node, 1))
                return _shape_bytes(shape, elem)
        return UNKNOWN

    def _guarded(self, enclosing, mod: ModuleSource) -> bool:
        scope = enclosing if enclosing is not None else mod.tree
        for node in ast.walk(scope):
            if isinstance(node, ast.Call):
                name = df.terminal_name(node.func).lower()
                segments = [s for s in name.split("_") if s]
                if any(s in _GUARD_HINTS for s in segments):
                    return True
        return False


def call_arg(call: ast.Call, i: int) -> Optional[ast.expr]:
    return call.args[i] if len(call.args) > i else None
