"""tracer-safety: no host syncs inside jitted/pallas code.

Inside a function being traced by ``jax.jit`` or ``pallas_call``,
``np.asarray(...)``, ``.item()``, ``float()/int()`` on traced values,
and Python ``if`` on tracer data either fail at trace time or -- worse
-- silently force a device->host sync per call, which is exactly the
per-op stall the PR 3 placement cache exists to avoid.

Scoped to the accelerator hot paths (``ops/`` and
``crush/vectorized.py``).  Traced scopes are found three ways:

* functions decorated ``@jax.jit`` / ``@partial(jax.jit, ...)``
  (static_argnames/static_argnums are honored: branching on a static
  arg is Python-level and fine; the vectorized mapper, whose ``self``
  is a pytree, branches on its static ``Structure`` by name);
* local functions passed by name to ``jax.jit(f)`` / ``pallas_call``;
* kernel *builders* whose call result feeds ``pallas_call(...)`` --
  their nested ``def kernel(...)`` bodies are the traced code.
"""

from __future__ import annotations

import ast
from typing import Iterable

from .. import astutil
from ..core import Finding, Module
from ..registry import Checker, register

_JIT_NAMES = {"jax.jit", "jit"}
_PALLAS_NAMES = {"pallas_call", "pl.pallas_call",
                 "pltpu.pallas_call"}
_PARTIAL_NAMES = {"partial", "functools.partial"}
_HOST_CALLS = {"np.asarray", "np.array", "numpy.asarray",
               "numpy.array", "onp.asarray", "onp.array",
               "jax.device_get", "device_get"}
_HOST_METHODS = {"item", "tolist"}
_HOST_BUILTINS = {"float", "int", "bool"}


def _jit_static_names(call: ast.Call,
                      params: list[str]) -> set[str]:
    """Parameter names made static by a jit(...) call's kwargs."""
    static: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                s = astutil.const_str(el)
                if s is not None:
                    static.add(s)
        elif kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                v = astutil.int_value(el)
                if v is not None and 0 <= v < len(params):
                    static.add(params[v])
    return static


def _params(fn: ast.AST) -> list[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs])


def _is_jit_target(call_func: ast.AST) -> bool:
    return (astutil.dotted(call_func) or "") in _JIT_NAMES


@register
class TracerSafety(Checker):
    name = "tracer-safety"
    description = ("host-sync calls or if-on-tracer inside jitted / "
                   "pallas code in the accelerator hot paths")

    def scope(self, module: Module) -> bool:
        p = module.path
        return ("ops/" in p or p.endswith("crush/vectorized.py")
                or "ops\\" in p)

    def check(self, module: Module) -> Iterable[Finding]:
        astutil.attach_parents(module.tree)
        defs: dict[str, list[ast.AST]] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        # (fn, tracer_params, include_own_body)
        traced: list[tuple[ast.AST, set[str], bool]] = []

        for fns in defs.values():
            for fn in fns:
                static = self._decorator_static(fn)
                if static is not None:
                    tracers = set(_params(fn)) - static
                    traced.append((fn, tracers, True))

        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = astutil.dotted(node.func) or ""
            if not (name in _JIT_NAMES or name in _PALLAS_NAMES):
                continue
            if not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Name) and arg.id in defs:
                for fn in defs[arg.id]:
                    static = _jit_static_names(node, _params(fn))
                    traced.append((fn, set(_params(fn)) - static,
                                   True))
            elif (isinstance(arg, ast.Call)
                  and isinstance(arg.func, ast.Name)
                  and arg.func.id in defs):
                # builder pattern: pallas_call(make_kernel(...)) --
                # the builder's params are config, its nested defs
                # are the traced kernels
                for fn in defs[arg.func.id]:
                    traced.append((fn, set(), False))

        seen: set[int] = set()
        for fn, tracers, own_body in traced:
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            yield from self._scan(fn, tracers, own_body, module)

    def _decorator_static(self, fn: ast.AST) -> set[str] | None:
        """If `fn` is jit-decorated, its static param names; else
        None."""
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = astutil.dotted(target) or ""
            if name in _JIT_NAMES:
                if isinstance(dec, ast.Call):
                    return _jit_static_names(dec, _params(fn))
                return set()
            if (isinstance(dec, ast.Call) and name in _PARTIAL_NAMES
                    and dec.args
                    and (astutil.dotted(dec.args[0]) or "")
                    in _JIT_NAMES):
                return _jit_static_names(dec, _params(fn))
        return None

    def _scan(self, fn: ast.AST, tracers: set[str], own_body: bool,
              module: Module) -> Iterable[Finding]:
        stack: list[tuple[ast.AST, set[str]]] = []
        if own_body:
            stack.append((fn, set(tracers)))
        else:
            for node in ast.walk(fn):
                if (node is not fn
                        and isinstance(node, (ast.FunctionDef,
                                              ast.AsyncFunctionDef))):
                    stack.append((node, set(_params(node))))
        emitted: set[tuple[int, str]] = set()
        for scope_fn, scope_tracers in stack:
            # nested defs (while_loop bodies etc.) run traced too;
            # their params are tracers
            all_tracers = set(scope_tracers)
            for node in ast.walk(scope_fn):
                if (node is not scope_fn
                        and isinstance(node, (ast.FunctionDef,
                                              ast.AsyncFunctionDef))):
                    all_tracers |= set(_params(node))
            for node in ast.walk(scope_fn):
                for f in self._scan_node(node, all_tracers, module):
                    key = (f.line, f.message)
                    if key not in emitted:
                        emitted.add(key)
                        yield f

    def _scan_node(self, node: ast.AST, tracers: set[str],
                   module: Module) -> Iterable[Finding]:
        if isinstance(node, ast.Call):
            name = astutil.dotted(node.func) or ""
            if name in _HOST_CALLS:
                yield Finding(
                    module.path, node.lineno, self.name,
                    f"host-sync call {name}() inside traced code; "
                    f"it blocks on device->host transfer every "
                    f"invocation (move it outside the jitted scope)")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _HOST_METHODS
                  and not node.args):
                yield Finding(
                    module.path, node.lineno, self.name,
                    f".{node.func.attr}() inside traced code forces "
                    f"a host sync; keep values on device")
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in _HOST_BUILTINS
                  and len(node.args) == 1
                  and not isinstance(node.args[0], ast.Constant)):
                yield Finding(
                    module.path, node.lineno, self.name,
                    f"{node.func.id}() on a traced value concretizes "
                    f"it (ConcretizationTypeError or a silent host "
                    f"sync); use jnp dtype casts instead")
        elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
            test = node.test
            if self._is_none_test(test):
                return
            names = astutil.names_in(test)
            if names and names <= tracers:
                yield Finding(
                    module.path, node.lineno, self.name,
                    f"Python branch on traced value(s) "
                    f"{', '.join(sorted(names))}; use jnp.where / "
                    f"lax.cond, or mark the argument static")

    @staticmethod
    def _is_none_test(test: ast.AST) -> bool:
        """`x is None` / `x is not None` branches are Python-level
        optionality, not tracer data flow."""
        return (isinstance(test, ast.Compare)
                and len(test.ops) == 1
                and isinstance(test.ops[0], (ast.Is, ast.IsNot)))
