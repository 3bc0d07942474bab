"""jit-stability: jitted callables must be built once and reused.

``jax.jit`` keys its compile cache on the *callable object* plus the
static argument values.  Two project-shaped ways to defeat it:

* constructing the jit inside a loop (a fresh callable every
  iteration -> recompile every iteration -- the per-epoch recompile
  hazard the PR 3 placement cache exists to amortize);
* jitting a method without marking ``self`` static: each tracer-typed
  ``self`` either fails (unhashable) or retraces per instance.  The
  sanctioned shapes: ``@partial(jax.jit, static_argnames=("self",
  ...))``, or a class registered as a pytree
  (``@jax.tree_util.register_pytree_node_class``, as the vectorized
  mapper is): its ``self`` flattens to arrays, which are operands, and
  a hashable static part, which is jit's key.
"""

from __future__ import annotations

import ast
from typing import Iterable

from .. import astutil
from ..core import Finding, Module
from ..registry import Checker, register

_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}


def _is_jit_call(node: ast.Call) -> bool:
    name = astutil.dotted(node.func) or ""
    if name in _JIT_NAMES:
        return True
    # partial(jax.jit, ...) used as a value (not a decorator)
    return (name in _PARTIAL_NAMES and node.args
            and (astutil.dotted(node.args[0]) or "") in _JIT_NAMES)


def _static_names(call: ast.Call) -> set[str]:
    out: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                s = astutil.const_str(el)
                if s is not None:
                    out.add(s)
        elif kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                v = astutil.int_value(el)
                if v is not None:
                    out.add(str(v))
    return out


@register
class JitStability(Checker):
    name = "jit-stability"
    description = ("jax.jit built inside a loop, or a method jitted "
                   "without static self (recompile hazards)")

    def check(self, module: Module) -> Iterable[Finding]:
        astutil.attach_parents(module.tree)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and _is_jit_call(node):
                yield from self._check_loop(node, module)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                yield from self._check_method(node, module)

    def _check_loop(self, node: ast.Call,
                    module: Module) -> Iterable[Finding]:
        fn = astutil.enclosing_function(node)
        for anc in astutil.ancestors(node):
            if anc is fn:
                break
            if isinstance(anc, (ast.For, ast.AsyncFor, ast.While)):
                yield Finding(
                    module.path, node.lineno, self.name,
                    "jax.jit constructed inside a loop: a fresh "
                    "callable per iteration misses the compile "
                    "cache and recompiles every time; hoist the "
                    "jitted function out of the loop")
                return

    def _check_method(self, fn: ast.AST,
                      module: Module) -> Iterable[Finding]:
        params = [a.arg for a in fn.args.args]
        if not params or params[0] != "self":
            return
        cls = astutil.parent(fn)
        if isinstance(cls, ast.ClassDef) and any(
                (astutil.dotted(d) or "").endswith(
                    "register_pytree_node_class")
                for d in cls.decorator_list):
            return
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = astutil.dotted(target) or ""
            static: set[str] | None = None
            if name in _JIT_NAMES:
                static = (_static_names(dec)
                          if isinstance(dec, ast.Call) else set())
            elif (isinstance(dec, ast.Call)
                  and name in _PARTIAL_NAMES and dec.args
                  and (astutil.dotted(dec.args[0]) or "")
                  in _JIT_NAMES):
                static = _static_names(dec)
            if static is None:
                continue
            if "self" not in static and "0" not in static:
                yield Finding(
                    module.path, fn.lineno, self.name,
                    f"method {fn.name}() jitted without "
                    f"static_argnames=('self', ...): self is traced "
                    f"(unhashable / retrace per call); mark it "
                    f"static as the vectorized mapper does")
