"""dynamic-gather: data-movement discipline in the Pallas kernel
modules.

Supersedes ``tools/check_no_dynamic_gather.py`` (now a shim): per-lane
dynamic gathers are the one data-movement primitive this hardware
cannot do at speed (the ~96 ms ``take_along_axis`` levels behind the
dense-regime loss of the pre-PR-1 chip bench) and Mosaic cannot lower
them in-kernel at all.  The legacy script matched call *names* only;
this rule adds the dataflow it punted on:

* aliased imports — ``from jax.numpy import take_along_axis as g`` /
  ``h = jnp.take`` are resolved through the module alias map;
* ``getattr`` indirection — ``getattr(jnp, "take")(...)`` flags like
  the direct call, and ``getattr(jnp, name)(...)`` with a
  non-constant attr on an array library flags as unauditable;
* ``x.at[idx].get()`` / ``.set()`` / ``.add()`` — the indexed-update
  forms the legacy tool explicitly left to review.

Suppression: ``# lint-ok: dynamic-gather: <reason>`` (the legacy
``# gather-ok: <reason>`` marker is still honoured).  The grid-carry
rule that used to share this module lives in
``tools/analysis/rules/grid_carry.py`` since round 8 (same rule name,
exit bit and suppression token; re-exported here for compatibility).
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Optional, Set

from tools.analysis.core import ModuleSource, Rule, Violation
from tools.analysis import dataflow as df
from tools.analysis.rules.grid_carry import (  # noqa: F401  (compat re-export)
    GridCarryRule,
    _kernel_module,
)

BANNED = {
    "take_along_axis",
    "take",
    "gather",
    "dynamic_slice",
    "dynamic_update_slice",
    "dynamic_index_in_dim",
    "searchsorted",
    "scatter",
    "scatter_add",
}

#: dotted-origin prefixes that count as "an array library" for the
#: getattr-indirection check.
_ARRAY_LIBS = ("jax.numpy", "jax.lax", "numpy", "jax")

_AT_METHODS = {"get", "set", "add", "mul", "min", "max", "apply"}


class DynamicGatherRule(Rule):
    name = "dynamic-gather"
    code = 4
    doc = ("no gather/scatter-shaped calls (incl. aliases, getattr "
           "indirection, .at[...] forms) in Pallas kernel modules")
    legacy_markers = ("# gather-ok:",)

    def applies(self, path: Path) -> bool:
        return path.suffix == ".py" and _kernel_module(path)

    def check(self, mod: ModuleSource) -> List[Violation]:
        aliases = df.build_aliases(mod.tree)
        out: List[Optional[Violation]] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            out.append(self._direct_or_aliased(mod, node, aliases))
            out.append(self._getattr_form(mod, node, aliases))
            out.append(self._at_form(mod, node))
        return [v for v in out if v is not None]

    def _flag(self, mod, lineno, name, how) -> Optional[Violation]:
        return self.violation(
            mod, lineno,
            f"dynamic-gather-shaped call '{name}' {how} in a Pallas "
            f"kernel module (the pattern behind the dense-regime "
            f"regression; use roll/sort/iota primitives, or annotate "
            f"the line with '# lint-ok: {self.name}: <reason>' if it "
            f"provably never runs on-chip)")

    def _direct_or_aliased(self, mod, node: ast.Call,
                           aliases) -> Optional[Violation]:
        name = df.terminal_name(node.func)
        if name in BANNED:
            return self._flag(mod, node.lineno, name, "")
        # a renamed import / assignment alias of a banned op
        if isinstance(node.func, ast.Name):
            origin = aliases.get(node.func.id, "")
            terminal = origin.rsplit(".", 1)[-1]
            if terminal in BANNED and terminal != node.func.id:
                return self._flag(mod, node.lineno, origin,
                                  f"(aliased as '{node.func.id}')")
        return None

    def _getattr_form(self, mod, node: ast.Call,
                      aliases) -> Optional[Violation]:
        fn = node.func
        if not (isinstance(fn, ast.Call)
                and df.terminal_name(fn.func) == "getattr"
                and len(fn.args) >= 2):
            return None
        obj, attr = fn.args[0], fn.args[1]
        origin = df.dotted_name(obj, aliases) or ""
        on_array_lib = any(
            origin == lib or origin.startswith(lib + ".")
            for lib in _ARRAY_LIBS)
        if isinstance(attr, ast.Constant) and isinstance(attr.value, str):
            if attr.value in BANNED:
                return self._flag(mod, node.lineno, attr.value,
                                  "(through getattr)")
            return None
        if on_array_lib:
            return self._flag(
                mod, node.lineno, f"getattr({origin}, <dynamic>)",
                "(unauditable dynamic attribute on an array library)")
        return None

    def _at_form(self, mod, node: ast.Call) -> Optional[Violation]:
        fn = node.func
        if (isinstance(fn, ast.Attribute) and fn.attr in _AT_METHODS
                and isinstance(fn.value, ast.Subscript)
                and isinstance(fn.value.value, ast.Attribute)
                and fn.value.value.attr == "at"):
            return self._flag(mod, node.lineno,
                              f".at[...].{fn.attr}", "(indexed update)")
        return None
