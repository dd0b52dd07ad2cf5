"""jit-recompile-hazard: concretizations and value-branching in traced code.

The PR 3 invariant is ZERO mid-stream recompiles; the runtime proves it
after the fact with ``rtfds_xla_recompiles_total``. This rule proves it
before runtime: starting from every ``jax.jit``/``pjit`` call site and
decorator, it walks the statically-resolvable call graph and runs a
small taint analysis — parameters of a jitted function are traced
values (minus ``static_argnums``/``static_argnames``), assignments
propagate taint, ``.shape``/``.ndim``/``.dtype``/``.size``/``len()``
launder it (shapes are static under trace). Inside that reachable set
it flags, at P0:

* ``.item()`` / ``.tolist()`` on a tainted value — host sync; under
  trace a ConcretizationTypeError, as a closure a silent per-value
  recompile;
* ``int()/float()/bool()/complex()`` of a tainted value — same;
* ``np.*`` calls with a tainted argument — numpy forces concretization;
* ``if``/``while``/``assert`` tests on a tainted value — Python-value
  branching retraces per distinct value;
* ``jnp.zeros/ones/full/empty/arange/linspace/eye`` whose shape/bound
  argument is tainted — non-static shape construction.

Approximation notes: resolution is lexical + one-level imports, so a
dynamically-chosen step function is invisible (the runtime recompile
detector stays the backstop); taint does not flow through containers.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..finding import Finding
from ..project import (FuncDef, Project, PyFile, dotted_name,
                       iter_own_nodes)
from ..registry import register

SHAPE_ATTRS = {"shape", "ndim", "dtype", "size", "weak_type", "sharding",
               "aval", "itemsize"}
CASTS = {"int", "float", "bool", "complex"}
SHAPE_BUILDERS = {"zeros", "ones", "full", "empty", "arange", "linspace",
                  "eye", "tri"}
JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}
_MAX_DEPTH = 24


def _numpy_aliases(pf: PyFile) -> Set[str]:
    return {local for local, dotted in pf.imports.items()
            if dotted == "numpy"}


def _jnp_aliases(pf: PyFile) -> Set[str]:
    return {local for local, dotted in pf.imports.items()
            if dotted in ("jax.numpy", "jax.experimental.numpy")}


class _Taint:
    """Per-function forward taint over simple assignments."""

    def __init__(self, tainted: Set[str],
                 static_attrs: Optional[Set[str]] = None) -> None:
        self.names = set(tainted)
        self.static_attrs = static_attrs or set()

    def expr(self, node: ast.AST) -> bool:
        """Does this expression (transitively) carry a traced value?"""
        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Attribute) \
                    and (n.attr in SHAPE_ATTRS
                         or n.attr in self.static_attrs):
                continue  # static under trace: launders taint
            if isinstance(n, ast.Call):
                fn = n.func
                if isinstance(fn, ast.Name) and fn.id == "len":
                    continue  # len() of a traced array is static
            if isinstance(n, ast.Name) and n.id in self.names:
                return True
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(n))
        return False


@register
class JitRecompileHazardRule:
    name = "jit-recompile-hazard"
    doc = ("tracer leaks / value-branching / non-static shapes in "
           "jit-reachable code (PR 3 zero-recompile invariant)")

    def run(self, project: Project) -> Iterable[Finding]:
        self.project = project
        self.findings: List[Finding] = []
        self._memo: Set[Tuple[str, str, frozenset]] = set()
        self._alias_cache: Dict[str, Tuple[Set[str], Set[str]]] = {}
        self._static_attrs = _static_property_names(project)
        for pf in project.target_files():
            if pf.tree is None:
                continue
            for fd, call in self._jit_sites(pf):
                root, static = self._jit_target(pf, fd, call)
                if root is None:
                    continue
                params = [p for p in _params_of(root.node)
                          if p not in static]
                self._analyze(root, frozenset(params), 0)
            for fd in pf.functions:
                static = self._decorator_static(fd.node)
                if static is None:
                    continue
                params = [p for p in fd.param_names()
                          if p not in static and p not in ("self", "cls")]
                self._analyze(fd, frozenset(params), 0)
        return self.findings

    # -- root discovery ----------------------------------------------------

    def _jit_sites(self, pf: PyFile):
        """(enclosing FuncDef|None, jit Call) pairs in one file."""
        seen_calls = set()
        for fd in pf.functions:
            for node in iter_own_nodes(fd.node):
                if isinstance(node, ast.Call) \
                        and dotted_name(node.func) in JIT_NAMES:
                    seen_calls.add(id(node))
                    yield fd, node
        for node in ast.walk(pf.tree):
            if isinstance(node, ast.Call) and id(node) not in seen_calls \
                    and dotted_name(node.func) in JIT_NAMES:
                yield None, node

    def _jit_target(self, pf: PyFile, scope: Optional[FuncDef],
                    call: ast.Call):
        """Resolve jax.jit(<target>, ...) → (FuncDef-ish, static names)."""
        if not call.args:
            return None, set()
        target = call.args[0]
        fd: Optional[FuncDef] = None
        if isinstance(target, ast.Lambda):
            fd = FuncDef(target, pf, f"<lambda@{target.lineno}>",
                         class_info=scope.class_info if scope else None,
                         parent=scope)
        elif isinstance(target, (ast.Name, ast.Attribute)):
            fake_call = ast.Call(func=target, args=[], keywords=[])
            fd = self.project.resolve_call(pf, scope, fake_call)
        if fd is None:
            return None, set()
        # static names resolve against the *resolved* def's parameter
        # list (static_argnums on a bare name needs the target's
        # params). jax.jit(self.step, …) receives a BOUND method: self
        # is already applied, so indices start at the first real param.
        bound = (isinstance(target, ast.Attribute)
                 and isinstance(target.value, ast.Name)
                 and target.value.id in ("self", "cls"))
        return fd, self._static_names(call, fd.node, bound=bound)

    def _static_names(self, call: ast.Call, target: ast.AST,
                      bound: bool = False) -> Set[str]:
        """static_argnums/static_argnames → parameter-name set.

        For an UNBOUND def (``jax.jit(step)``, decorator on a method),
        jax's static_argnums counts ``self`` as position 0, so indexing
        uses the full parameter list; for a BOUND target
        (``jax.jit(self.step)``), self is already applied and indices
        start at the first real parameter."""
        params: List[str] = _params_full(target) if isinstance(
            target, (ast.Lambda, ast.FunctionDef,
                     ast.AsyncFunctionDef)) else []
        if bound and params and params[0] in ("self", "cls"):
            params = params[1:]
        out: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                out.update(_const_strs(kw.value))
            elif kw.arg == "static_argnums":
                for i in _const_ints(kw.value):
                    if 0 <= i < len(params):
                        out.add(params[i])
                    elif not params:
                        out.add(f"<pos{i}>")
        return out

    def _decorator_static(self, node: ast.AST) -> Optional[Set[str]]:
        """static-name set when decorated @jax.jit / @partial(jax.jit,…)."""
        for dec in getattr(node, "decorator_list", []):
            if dotted_name(dec) in JIT_NAMES:
                return set()
            if isinstance(dec, ast.Call):
                dn = dotted_name(dec.func)
                if dn in JIT_NAMES:
                    return self._static_names(dec, node)
                if dn in ("partial", "functools.partial") and dec.args \
                        and dotted_name(dec.args[0]) in JIT_NAMES:
                    return self._static_names(dec, node)
        return None

    # -- taint walk --------------------------------------------------------

    def _analyze(self, fd: FuncDef, tainted_params: frozenset,
                 depth: int) -> None:
        key = (fd.file.relpath, fd.qualname, tainted_params)
        if key in self._memo or depth > _MAX_DEPTH or not tainted_params:
            return
        self._memo.add(key)
        taint = _Taint(set(tainted_params), self._static_attrs)
        pf = fd.file
        body = fd.node.body
        if not isinstance(body, list):  # Lambda
            self._check_expr(pf, fd, body, taint, depth)
            return
        self._stmts(pf, fd, body, taint, depth)

    def _stmts(self, pf: PyFile, fd: FuncDef, stmts: List[ast.stmt],
               taint: _Taint, depth: int) -> None:
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(s, ast.Assign):
                self._check_expr(pf, fd, s.value, taint, depth)
                is_t = taint.expr(s.value)
                for tgt in s.targets:
                    _retaint_target(tgt, is_t, taint)
            elif isinstance(s, ast.AnnAssign) and s.value is not None:
                self._check_expr(pf, fd, s.value, taint, depth)
                if isinstance(s.target, ast.Name):
                    (taint.names.add(s.target.id) if taint.expr(s.value)
                     else taint.names.discard(s.target.id))
            elif isinstance(s, ast.AugAssign):
                self._check_expr(pf, fd, s.value, taint, depth)
                if isinstance(s.target, ast.Name) and taint.expr(s.value):
                    taint.names.add(s.target.id)
            elif isinstance(s, (ast.If, ast.While)):
                self._check_expr(pf, fd, s.test, taint, depth)
                if not _identity_test(s.test) and taint.expr(s.test):
                    self._emit(pf, s.test,
                               "Python-value branching on a traced value "
                               "(retrace per distinct value, or "
                               "ConcretizationTypeError)", fd)
                self._stmts(pf, fd, s.body, taint, depth)
                self._stmts(pf, fd, s.orelse, taint, depth)
            elif isinstance(s, ast.Assert):
                self._check_expr(pf, fd, s.test, taint, depth)
                if taint.expr(s.test):
                    self._emit(pf, s.test,
                               "assert on a traced value (concretizes "
                               "under trace)", fd)
            elif isinstance(s, ast.For):
                self._check_expr(pf, fd, s.iter, taint, depth)
                if taint.expr(s.iter):
                    for n in ast.walk(s.target):
                        if isinstance(n, ast.Name):
                            taint.names.add(n.id)
                self._stmts(pf, fd, s.body, taint, depth)
                self._stmts(pf, fd, s.orelse, taint, depth)
            elif isinstance(s, ast.With):
                for item in s.items:
                    self._check_expr(pf, fd, item.context_expr, taint,
                                     depth)
                self._stmts(pf, fd, s.body, taint, depth)
            elif isinstance(s, ast.Try):
                self._stmts(pf, fd, s.body, taint, depth)
                for h in s.handlers:
                    self._stmts(pf, fd, h.body, taint, depth)
                self._stmts(pf, fd, s.orelse, taint, depth)
                self._stmts(pf, fd, s.finalbody, taint, depth)
            elif isinstance(s, ast.Match):
                self._check_expr(pf, fd, s.subject, taint, depth)
                if taint.expr(s.subject):
                    self._emit(pf, s.subject,
                               "match on a traced value (structural "
                               "patterns concretize under trace)", fd)
                for case in s.cases:
                    if case.guard is not None:
                        self._check_expr(pf, fd, case.guard, taint,
                                         depth)
                        if taint.expr(case.guard):
                            self._emit(pf, case.guard,
                                       "Python-value branching on a "
                                       "traced value (retrace per "
                                       "distinct value, or "
                                       "ConcretizationTypeError)", fd)
                    self._stmts(pf, fd, case.body, taint, depth)
            else:
                for child in ast.iter_child_nodes(s):
                    if isinstance(child, ast.expr):
                        self._check_expr(pf, fd, child, taint, depth)

    def _check_expr(self, pf: PyFile, fd: FuncDef, expr: ast.AST,
                    taint: _Taint, depth: int) -> None:
        # manual stack so nested lambda/def bodies are PRUNED (their
        # params shadow outer names; ast.walk would still visit them
        # and report false positives against the outer taint env)
        stack = [expr]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Call):
                self._check_call(pf, fd, n, taint, depth)
            elif isinstance(n, ast.IfExp) \
                    and not _identity_test(n.test) \
                    and taint.expr(n.test):
                # `a if cond else b` branches exactly like an if stmt
                self._emit(pf, n.test,
                           "Python-value branching on a traced value "
                           "(retrace per distinct value, or "
                           "ConcretizationTypeError)", fd)
            stack.extend(ast.iter_child_nodes(n))

    def _check_call(self, pf: PyFile, fd: FuncDef, call: ast.Call,
                    taint: _Taint, depth: int) -> None:
        fn = call.func
        if isinstance(fn, ast.Attribute):
            if fn.attr in ("item", "tolist") and not call.args \
                    and taint.expr(fn.value):
                self._emit(pf, call,
                           f".{fn.attr}() on a traced value (host "
                           "concretization — trace-time crash or "
                           "silent per-value recompile)", fd)
                return
            dn = dotted_name(fn)
            root = dn.split(".", 1)[0] if dn else ""
            np_al, jnp_al = self._aliases(pf)
            if root in np_al and (
                    any(taint.expr(a) for a in call.args)
                    or any(taint.expr(kw.value) for kw in call.keywords)):
                self._emit(pf, call,
                           f"{dn}() on a traced value (numpy forces "
                           "concretization/device sync)", fd)
                return
            if root in jnp_al and fn.attr in SHAPE_BUILDERS:
                shape_args = call.args[:1] + [
                    kw.value for kw in call.keywords
                    if kw.arg in ("shape", "stop", "N")]
                if any(taint.expr(a) for a in shape_args):
                    self._emit(pf, call,
                               f"{dn}() with a traced shape/bound "
                               "argument (non-static shape "
                               "construction)", fd)
                    return
        elif isinstance(fn, ast.Name):
            if fn.id in CASTS and len(call.args) == 1 \
                    and taint.expr(call.args[0]):
                self._emit(pf, call,
                           f"{fn.id}() of a traced value (host "
                           "concretization)", fd)
                return
        # interprocedural: taint flows into resolvable callees
        tgt = self.project.resolve_call(pf, fd, call)
        if tgt is None or not isinstance(tgt.node, (ast.FunctionDef,
                                                    ast.AsyncFunctionDef)):
            return
        params = tgt.param_names()
        if params and tgt.class_info is not None and params[0] in ("self",
                                                                   "cls"):
            params = params[1:]
        flowed: Set[str] = set()
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred):
                continue
            if i < len(params) and taint.expr(a):
                flowed.add(params[i])
        for kw in call.keywords:
            if kw.arg and kw.arg in params and taint.expr(kw.value):
                flowed.add(kw.arg)
        if flowed:
            self._analyze(tgt, frozenset(flowed), depth + 1)

    def _aliases(self, pf: PyFile) -> Tuple[Set[str], Set[str]]:
        got = self._alias_cache.get(pf.relpath)
        if got is None:
            got = (_numpy_aliases(pf), _jnp_aliases(pf))
            self._alias_cache[pf.relpath] = got
        return got

    def _emit(self, pf: PyFile, node: ast.AST, msg: str,
              fd: FuncDef) -> None:
        self.findings.append(Finding(
            rule=self.name, severity="P0", path=pf.relpath,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=msg,
            context=f"{pf.module}:{fd.qualname}"))


def _params_full(node: ast.AST) -> List[str]:
    """Positional parameter names INCLUDING self/cls (index-accurate)."""
    a = getattr(node, "args", None)
    if a is None:
        return []
    return [p.arg for p in list(a.posonlyargs) + list(a.args)
            + list(a.kwonlyargs)]


def _params_of(node: ast.AST) -> List[str]:
    return [n for n in _params_full(node) if n not in ("self", "cls")]


def _const_strs(node: ast.AST) -> List[str]:
    out = []
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            out.append(n.value)
    return out


def _retaint_target(tgt: ast.AST, is_tainted: bool,
                    taint: _Taint) -> None:
    """Apply an assignment's taint to its target.

    Only plain-Name bindings change a name's taint; an attribute or
    subscript store (``obj.y = v`` / ``d[k] = v``) rebinds NOTHING —
    walking it would wrongly taint/launder the base object name.
    """
    if isinstance(tgt, ast.Name):
        (taint.names.add(tgt.id) if is_tainted
         else taint.names.discard(tgt.id))
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        for elt in tgt.elts:
            _retaint_target(elt, is_tainted, taint)
    elif isinstance(tgt, ast.Starred):
        _retaint_target(tgt.value, is_tainted, taint)


def _identity_test(test: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` — identity never concretizes."""
    if isinstance(test, ast.Compare) \
            and all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return True
    if isinstance(test, ast.BoolOp):
        return all(_identity_test(v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _identity_test(test.operand)
    return False


def _static_property_names(project: Project) -> Set[str]:
    """Names of @property methods whose body derives only from shapes.

    ``WindowState.capacity`` → ``self.bucket_day.shape[0] //
    self.n_buckets`` (a shape over static pytree metadata) is static
    under trace; accessing ``.capacity`` on a traced state launders
    taint. Name-based across the package (documented approximation):
    a name qualifies only if EVERY property of that name in the
    package is shape-derived.
    """
    shapey: Set[str] = set()
    traced: Set[str] = set()
    probe = _Taint({"self"})
    for pf in project.target_files():
        for fd in pf.functions:
            if fd.class_info is None or not isinstance(
                    fd.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(dotted_name(d) in ("property", "functools."
                       "cached_property", "cached_property")
                       for d in fd.node.decorator_list):
                continue
            ann = fd.node.returns
            if isinstance(ann, ast.Name) and ann.id in ("int", "float",
                                                        "bool", "str"):
                shapey.add(fd.name)  # annotated Python scalar: static
                continue
            rets = [s for s in ast.walk(fd.node)
                    if isinstance(s, ast.Return) and s.value is not None]
            if rets and all(not probe.expr(r.value) for r in rets):
                shapey.add(fd.name)
            else:
                traced.add(fd.name)
    return shapey - traced


def _const_ints(node: ast.AST) -> List[int]:
    out = []
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, int) \
                and not isinstance(n.value, bool):
            out.append(n.value)
    return out
