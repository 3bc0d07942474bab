"""x64-scope: jax_enable_x64 is thread-local-context only.

The PR 1 hazard: flipping ``jax_enable_x64`` globally (at import time
or anywhere else) changes dtype semantics for *every* computation in
the process -- the CRUSH mapper once needed x64 for its straw2 draw
(it computes in 32-bit limbs since PR 32 and holds no 64-bit type),
and the EC GF(2) kernels and everything jitted elsewhere must keep the
default.  The sanctioned mechanism is the scoped context manager
(``jax.enable_x64``); nothing in the package uses it today.

Flagged everywhere, with no sanctioned call sites:

* ``<anything>.config.update("jax_enable_x64", ...)`` (covers
  ``jax.config.update`` and ``from jax import config`` forms);
* attribute assignment to ``jax_enable_x64`` (the
  ``jax.config.jax_enable_x64 = True`` back door).
"""

from __future__ import annotations

import ast
from typing import Iterable

from .. import astutil
from ..core import Finding, Module
from ..registry import Checker, register

_FLAG = "jax_enable_x64"


@register
class X64Scope(Checker):
    name = "x64-scope"
    description = ("jax_enable_x64 mutated outside the enable_x64 "
                   "context manager")

    def check(self, module: Module) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                yield from self._check_call(node, module)
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    yield from self._check_target(tgt, node, module)
            elif isinstance(node, ast.AugAssign):
                yield from self._check_target(node.target, node,
                                              module)

    def _check_call(self, node: ast.Call,
                    module: Module) -> Iterable[Finding]:
        name = astutil.dotted(node.func) or ""
        if not (name == "update" or name.endswith("config.update")
                or name.endswith(".update")):
            return
        if not node.args:
            return
        if astutil.const_str(node.args[0]) != _FLAG:
            return
        yield Finding(
            module.path, node.lineno, self.name,
            f"global {_FLAG} flip via {name}(); use the scoped "
            f"jax.experimental.enable_x64 context manager instead")

    def _check_target(self, tgt: ast.AST, node: ast.AST,
                      module: Module) -> Iterable[Finding]:
        if isinstance(tgt, ast.Attribute) and tgt.attr == _FLAG:
            yield Finding(
                module.path, node.lineno, self.name,
                f"direct assignment to {astutil.dotted(tgt)}; use "
                f"the scoped jax.experimental.enable_x64 context "
                f"manager instead")
