"""donated-buffer-aliasing: no reads of a buffer a launch consumed.

The sharded data plane (parallel/mesh_codec.py) passes stripe buffers
to ``jax.jit(..., donate_argnums=...)``-compiled launches: the launch
OWNS the donated device buffer -- XLA may alias it into the output (the
RMW in-place update) or free it mid-execution.  Reading the Python
name again after the call returns garbage-or-crash depending on
backend and phase of the moon, which is exactly the class of bug a
test on one backend does not catch.  ROADMAP queued this rule the day
the data plane adopted donation: *a donated array read after the
launch that consumed it is a use-after-donate*.

Detection is best-effort by construction, like the rest of the call
graph layer:

* a *donating callable* is a name bound to ``jax.jit``/``pjit`` (or a
  function decorated with either) carrying a literal
  ``donate_argnums``;
* donation PROPAGATES interprocedurally: a function that forwards its
  own parameter into a donated position is itself donating that
  parameter (fixpoint over the project), so a caller module away from
  the jit still gets flagged;
* at every call site of a donating callable, an argument spelled as a
  plain name that is READ again after the call -- before any
  re-binding of the name -- is a finding.

Scoped to jax-importing modules: donation is a jax contract; nothing
else produces these buffers.
"""

from __future__ import annotations

import ast
from typing import Iterable

from .device_path import _imports_top
from .. import astutil
from ..callgraph import CallGraph, own_nodes
from ..core import Finding
from ..registry import ProjectChecker, register

_JIT_LEAVES = {"jit", "pjit"}

# Launch wrappers whose donation the AST cannot see (the jit carrying
# donate_argnums comes out of a cached compile factory, so no literal
# reaches the call site): ``("Class.method", (call-arg indices after
# self,))`` entries are seeded into the donor fixpoint by name, the
# way device_path.ROOTS anchors reachability.  None today: MeshCodec
# hands each factory-made launch a fresh ``_put`` result and binds no
# name to a donated buffer (tests/test_lint.py drives the mechanism
# with a fixture of its own).
ROOTS: tuple[tuple[str, tuple[int, ...]], ...] = ()


def _donated_positions(call: ast.Call) -> tuple[int, ...] | None:
    """Literal donate_argnums of a jit/pjit call, or None."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for el in v.elts:
                if not (isinstance(el, ast.Constant)
                        and isinstance(el.value, int)):
                    return None
                out.append(el.value)
            return tuple(out)
        return None
    return None


def _is_jit(call: ast.Call, syms) -> bool:
    leaf = astutil.name_leaf(call.func)
    if leaf not in _JIT_LEAVES:
        return False
    dotted = astutil.dotted(call.func)
    if dotted is None or "." not in dotted:
        # bare `jit(...)`: accept when imported from jax
        return syms.expand_alias(leaf).startswith("jax")
    head = dotted.split(".", 1)[0]
    return syms.expand_alias(head).startswith("jax")


def _params(node) -> list[str]:
    a = node.args
    return [p.arg for p in (a.posonlyargs + a.args)]


@register
class DonatedBufferAliasing(ProjectChecker):
    name = "donated-buffer-aliasing"
    description = ("a buffer read after being passed into a donated "
                   "(donate_argnums) launch position -- the launch "
                   "owns it; reading it back is use-after-donate")

    def check_project(self, graph: CallGraph) -> Iterable[Finding]:
        in_scope = {
            path for path, syms in graph.symbols.items()
            if _imports_top(syms.module.tree, "jax")}
        if not in_scope:
            return
        # donors: callee key -> donated CALL-ARG positions.  Keys:
        # ("mod", path, name) for module-level jit bindings,
        # ("fn", qualname) for functions (decorated or propagated).
        donors: dict[tuple, tuple[int, ...]] = {}
        for path in in_scope:
            syms = graph.symbols[path]
            for node in ast.walk(syms.module.tree):
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and _is_jit(node.value, syms)):
                    pos = _donated_positions(node.value)
                    if pos:
                        for tgt in node.targets:
                            if isinstance(tgt, ast.Name):
                                donors[("mod", path, tgt.id)] = pos
            for fi in syms.functions:
                if fi.path != path:
                    continue
                for dec in fi.node.decorator_list:
                    if isinstance(dec, ast.Call):
                        pos = (_donated_positions(dec)
                               if self._jitlike_decorator(dec, syms)
                               else None)
                        if pos:
                            # param index -> call-arg index (methods
                            # drop the explicit self at the call site)
                            off = 1 if fi.cls else 0
                            donors[("fn", fi.qualname)] = tuple(
                                p - off for p in pos if p - off >= 0)
        # declared donor ROOTS: launch wrappers around factory-made
        # donating executables
        for spec, pos in ROOTS:
            for qual in graph.lookup(spec):
                fi = graph.functions.get(qual)
                if fi is not None and fi.path in in_scope:
                    merged = set(donors.get(("fn", qual), ())) | set(pos)
                    donors[("fn", qual)] = tuple(sorted(merged))

        # interprocedural fixpoint: forwarding a parameter into a
        # donated position makes the forwarder a donor of that param
        for _ in range(6):
            grew = False
            for path in in_scope:
                syms = graph.symbols[path]
                for fi in syms.functions:
                    params = _params(fi.node)
                    off = 1 if fi.cls and params[:1] == ["self"] else 0
                    mine: set[int] = set(
                        donors.get(("fn", fi.qualname), ()))
                    before = len(mine)
                    for call, pos in self._donating_calls(
                            fi, syms, graph, donors):
                        for p in pos:
                            if p >= len(call.args):
                                continue
                            arg = call.args[p]
                            if isinstance(arg, ast.Name) \
                                    and arg.id in params:
                                cp = params.index(arg.id) - off
                                if cp >= 0:
                                    mine.add(cp)
                    if len(mine) > before:
                        donors[("fn", fi.qualname)] = tuple(
                            sorted(mine))
                        grew = True
            if not grew:
                break

        for path in sorted(in_scope):
            syms = graph.symbols[path]
            for fi in syms.functions:
                yield from self._check_function(fi, syms, graph,
                                                donors)

    @staticmethod
    def _jitlike_decorator(dec: ast.Call, syms) -> bool:
        """``@jax.jit(...)`` / ``@partial(jax.jit, ...)`` forms."""
        if _is_jit(dec, syms):
            return True
        leaf = astutil.name_leaf(dec.func)
        if leaf != "partial" or not dec.args:
            return False
        inner = dec.args[0]
        leaf0 = astutil.name_leaf(inner)
        if leaf0 not in _JIT_LEAVES:
            return False
        head = (astutil.dotted(inner) or leaf0).split(".", 1)[0]
        return syms.expand_alias(head).startswith("jax")

    def _donating_calls(self, fi, syms, graph: CallGraph,
                        donors: dict):
        """(call node, donated call-arg positions) sites in ``fi``,
        including calls through local jit bindings made inside it."""
        local: dict[str, tuple[int, ...]] = {}
        for node in own_nodes(fi.node):
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and _is_jit(node.value, syms)):
                pos = _donated_positions(node.value)
                if pos:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            local[tgt.id] = pos
        for node in own_nodes(fi.node):
            if not isinstance(node, ast.Call):
                continue
            pos = self._resolve_donor(node, fi, syms, graph, donors,
                                      local)
            if pos:
                yield node, pos

    @staticmethod
    def _resolve_donor(call: ast.Call, fi, syms, graph: CallGraph,
                       donors: dict, local: dict):
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
            if name in local:
                return local[name]
            hit = donors.get(("mod", fi.path, name))
            if hit:
                return hit
            tf = syms.top_funcs.get(name)
            if tf is not None:
                return donors.get(("fn", tf.qualname))
            target = syms.aliases.get(name)
            if target and "." in target:
                mod, _, leaf = target.rpartition(".")
                msyms = graph.module_by_dotted.get(mod)
                if msyms is not None:
                    hit = donors.get(("mod", msyms.module.path, leaf))
                    if hit:
                        return hit
                    tf = msyms.top_funcs.get(leaf)
                    if tf is not None:
                        return donors.get(("fn", tf.qualname))
            return None
        if isinstance(func, ast.Attribute) and fi.cls \
                and isinstance(func.value, ast.Name) \
                and func.value.id == "self":
            ci = syms.classes.get(fi.cls)
            if ci is not None:
                meth = ci.methods.get(func.attr)
                if meth is not None:
                    return donors.get(("fn", meth.qualname))
        return None

    def _check_function(self, fi, syms, graph: CallGraph,
                        donors: dict) -> Iterable[Finding]:
        sites = list(self._donating_calls(fi, syms, graph, donors))
        if not sites:
            return
        # name -> [(load lineno, node)], [store linenos]
        loads: dict[str, list[int]] = {}
        stores: dict[str, list[int]] = {}
        for node in own_nodes(fi.node):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Load):
                    loads.setdefault(node.id, []).append(node.lineno)
                else:
                    stores.setdefault(node.id, []).append(node.lineno)
        for call, pos in sites:
            end = getattr(call, "end_lineno", call.lineno)
            for p in pos:
                if p >= len(call.args):
                    continue
                arg = call.args[p]
                if not isinstance(arg, ast.Name):
                    continue
                name = arg.id
                rebinds = [ln for ln in stores.get(name, ())
                           if ln >= call.lineno]
                horizon = min(rebinds) if rebinds else 10 ** 9
                bad = [ln for ln in loads.get(name, ())
                       if end < ln < horizon]
                if bad:
                    yield Finding(
                        fi.path, min(bad), self.name,
                        f"`{name}` read after the launch at line "
                        f"{call.lineno} consumed it (donated arg "
                        f"position {p}): the launch owns a donated "
                        f"buffer -- read before the launch, re-bind "
                        f"the name, or copy first")
