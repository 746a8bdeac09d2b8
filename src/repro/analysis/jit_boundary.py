"""Jit-boundary pass.

Builds a call graph rooted at every ``jax.jit``-ed function and checks
the code reachable under tracing for

- **host syncs** (rule ``host-sync``): ``.item()`` on a tracer,
  ``float()/int()/bool()/np.asarray()/np.array()`` applied to a traced
  value, and wall-clock reads (``time.time`` / ``perf_counter`` /
  ``monotonic``) anywhere in jit scope;
- **Python branching on traced values** (rule ``traced-branch``):
  ``if``/``while`` whose test depends on a tracer (``is None`` /
  membership tests and shape/dtype-derived values are static and
  exempt);
- **unhashable static args** (rule ``static-unhashable``): a
  ``static_argnames`` parameter fed a ``list``/``set``/``dict`` display
  at a call site (lists are unhashable -> retrace error at runtime).

Root discovery understands the repo's three idioms:
``@functools.partial(jax.jit, static_argnames=...)`` decorators,
direct ``jax.jit(fn)`` calls on local defs, and the factory pattern
``jax.jit(make_X(cfg, ...))`` — resolved through imports to ``make_X``'s
returned inner ``def``s (``make_decode_step``, ``make_prefill_step``,
``make_train_step``).

Tracedness is propagated interprocedurally: a function called with a
traced argument is analysed with those parameters traced (memoised).
Closure variables (``cfg``, ``run_cfg``, ``max_len``) are static, which
is what makes config-dependent Python dispatch legal under jit.

``# jit-ok`` on the offending line suppresses a finding.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.findings import Finding, rel

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}
_TIME_FUNCS = {"time", "perf_counter", "monotonic", "process_time"}
_ARRAY_NS = {"jnp", "jax", "lax", "np_like"}
# jax.sharding queries answered from the trace context (the mesh), not data
_STATIC_SHARDING_QUERIES = {"get_abstract_mesh"}
_STATIC_BUILTINS = {"len", "isinstance", "getattr", "hasattr", "type",
                    "range", "sorted", "min", "max", "enumerate", "zip",
                    "tuple", "list", "dict", "set", "str", "repr"}
_CAST_BUILTINS = {"float", "int", "bool", "complex"}


@dataclass
class _Module:
    name: str                       # dotted module path
    path: Path
    tree: ast.Module
    lines: List[str]
    functions: Dict[str, ast.FunctionDef] = field(default_factory=dict)
    # local alias -> ("module", dotted) or ("from", module, name)
    imports: Dict[str, Tuple] = field(default_factory=dict)
    # var name -> Call exprs assigned to it (``step = make_decode_step(...)``)
    var_calls: Dict[str, List[ast.Call]] = field(default_factory=dict)


def _index_module(name: str, path: Path) -> _Module:
    source = path.read_text()
    tree = ast.parse(source, filename=str(path))
    mod = _Module(name=name, path=path, tree=tree, lines=source.splitlines())
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name not in mod.functions:
            mod.functions[node.name] = node
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)):
            mod.var_calls.setdefault(node.targets[0].id, []).append(node.value)
    for node in tree.body:
        if isinstance(node, ast.Import):
            for a in node.names:
                mod.imports[a.asname or a.name.split(".")[0]] = ("module", a.name)
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                mod.imports[a.asname or a.name] = ("from", node.module, a.name)
    return mod


def _is_jax_jit(node: ast.expr) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name) and node.value.id == "jax")


def _static_argnames(call: ast.Call) -> Set[str]:
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                for elt in kw.value.elts:
                    if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                        names.add(elt.value)
            elif isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, str):
                names.add(kw.value.value)
    return names


def _returned_defs(fn: ast.FunctionDef) -> List[ast.FunctionDef]:
    """Inner defs that a factory returns (the actual jitted callables)."""
    local_defs: Dict[str, List[ast.FunctionDef]] = {}
    for n in ast.walk(fn):
        if isinstance(n, ast.FunctionDef) and n is not fn:
            local_defs.setdefault(n.name, []).append(n)
    returned = {node.value.id for node in ast.walk(fn)
                if isinstance(node, ast.Return)
                and isinstance(node.value, ast.Name)}
    out: List[ast.FunctionDef] = []
    for name in returned:
        # a factory may define several same-named variants on different
        # branches (make_prefill_step): every one is a jit root
        out.extend(local_defs.get(name, []))
    return out


class JitBoundaryPass:
    def __init__(self, files: Dict[str, Path], root: Path) -> None:
        self.root = root
        self.modules: Dict[str, _Module] = {
            name: _index_module(name, p) for name, p in files.items()
        }
        self.findings: List[Finding] = []
        self._seen_keys: Set[Tuple] = set()
        self._memo: Set[Tuple] = set()
        self._stack: Set[Tuple[str, int]] = set()

    # -- root discovery --------------------------------------------------
    def discover_roots(self) -> List[Tuple[_Module, ast.FunctionDef, Set[str]]]:
        roots: List[Tuple[_Module, ast.FunctionDef, Set[str]]] = []
        seen: Set[Tuple[str, int]] = set()

        def add(mod: _Module, fn: ast.FunctionDef, static: Set[str]) -> None:
            key = (mod.name, fn.lineno)
            if key not in seen:
                seen.add(key)
                roots.append((mod, fn, static))

        for mod in self.modules.values():
            # decorator form
            for fn in [n for n in ast.walk(mod.tree)
                       if isinstance(n, ast.FunctionDef)]:
                for dec in fn.decorator_list:
                    if _is_jax_jit(dec):
                        add(mod, fn, set())
                    elif (isinstance(dec, ast.Call)
                          and self._is_partial(dec.func, mod)
                          and dec.args and _is_jax_jit(dec.args[0])):
                        add(mod, fn, _static_argnames(dec))
            # call form: jax.jit(<Name>) / jax.jit(make_X(...))
            for call in [n for n in ast.walk(mod.tree)
                         if isinstance(n, ast.Call) and _is_jax_jit(n.func)]:
                if not call.args:
                    continue
                static = _static_argnames(call)
                target = call.args[0]
                if isinstance(target, ast.Name):
                    fn = mod.functions.get(target.id) or self._local_def(
                        mod, target.id)
                    if fn is not None:
                        add(mod, fn, static)
                    else:
                        # jax.jit(step) where step = make_X(...)
                        for assigned in mod.var_calls.get(target.id, ()):
                            factory = self._resolve_callable(
                                mod, assigned.func)
                            if factory is not None:
                                fmod, fdef = factory
                                for inner in _returned_defs(fdef):
                                    add(fmod, inner, static)
                elif isinstance(target, ast.Call):
                    factory = self._resolve_callable(mod, target.func)
                    if factory is not None:
                        fmod, fdef = factory
                        for inner in _returned_defs(fdef):
                            add(fmod, inner, static)
        return roots

    @staticmethod
    def _is_partial(func: ast.expr, mod: _Module) -> bool:
        if isinstance(func, ast.Name) and func.id == "partial":
            return True
        return (isinstance(func, ast.Attribute) and func.attr == "partial"
                and isinstance(func.value, ast.Name)
                and func.value.id == "functools")

    def _local_def(self, mod: _Module, name: str) -> Optional[ast.FunctionDef]:
        for n in ast.walk(mod.tree):
            if isinstance(n, ast.FunctionDef) and n.name == name:
                return n
        return None

    def _resolve_callable(
        self, mod: _Module, func: ast.expr
    ) -> Optional[Tuple[_Module, ast.FunctionDef]]:
        """Resolve a called name/attribute to (module, def) across imports."""
        if isinstance(func, ast.Name):
            if func.id in mod.functions:
                return (mod, mod.functions[func.id])
            imp = mod.imports.get(func.id)
            if imp and imp[0] == "from":
                target = self.modules.get(imp[1])
                if target and imp[2] in target.functions:
                    return (target, target.functions[imp[2]])
            # calling a variable bound to a factory's return value:
            # ``step = make_decode_step(cfg); ... step(params, ...)``
            for assigned in mod.var_calls.get(func.id, ()):
                if (isinstance(assigned.func, ast.Name)
                        and assigned.func.id == func.id):
                    continue  # self-referential rebind, e.g. f = f(...)
                factory = self._resolve_callable(mod, assigned.func)
                if factory is not None:
                    fmod, fdef = factory
                    inner = _returned_defs(fdef)
                    if inner:
                        return (fmod, inner[0])
        elif isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
            imp = mod.imports.get(func.value.id)
            modname = None
            if imp and imp[0] == "module":
                modname = imp[1]
            elif imp and imp[0] == "from":
                modname = f"{imp[1]}.{imp[2]}"
            if modname:
                target = self.modules.get(modname)
                if target and func.attr in target.functions:
                    return (target, target.functions[func.attr])
        return None

    # -- analysis --------------------------------------------------------
    def run(self) -> List[Finding]:
        for mod, fn, static in self.discover_roots():
            traced = {a.arg for a in fn.args.args + fn.args.kwonlyargs
                      if a.arg not in static and a.arg != "self"}
            self._analyze(mod, fn, traced)
            self._check_static_call_sites(mod, fn, static)
        return self.findings

    def _emit(self, mod: _Module, line: int, rule: str, symbol: str,
              message: str) -> None:
        if line <= len(mod.lines) and "# jit-ok" in mod.lines[line - 1]:
            return
        key = (mod.name, line, rule, symbol)
        if key in self._seen_keys:
            return
        self._seen_keys.add(key)
        self.findings.append(Finding(
            pass_name="jit", rule=rule, file=rel(mod.path, self.root),
            line=line, symbol=symbol, message=message))

    def _analyze(self, mod: _Module, fn: ast.FunctionDef,
                 traced_params: Set[str]) -> None:
        memo_key = (mod.name, fn.lineno, frozenset(traced_params))
        if memo_key in self._memo:
            return
        stack_key = (mod.name, fn.lineno)
        if stack_key in self._stack:
            return
        self._memo.add(memo_key)
        self._stack.add(stack_key)
        try:
            _FunctionAnalyzer(self, mod, fn, traced_params).run()
        finally:
            self._stack.discard(stack_key)

    def _check_static_call_sites(self, mod: _Module, fn: ast.FunctionDef,
                                 static: Set[str]) -> None:
        """Unhashable values bound to static params at call sites of the
        jitted function (by keyword, or positionally via the def)."""
        if not static:
            return
        pos_names = [a.arg for a in fn.args.args]
        for call in [n for n in ast.walk(mod.tree)
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Name)
                     and n.func.id == fn.name]:
            bound: List[Tuple[str, ast.expr]] = []
            for i, arg in enumerate(call.args):
                if i < len(pos_names):
                    bound.append((pos_names[i], arg))
            for kw in call.keywords:
                if kw.arg:
                    bound.append((kw.arg, kw.value))
            for name, value in bound:
                if name in static and isinstance(
                        value, (ast.List, ast.Set, ast.Dict, ast.ListComp,
                                ast.SetComp, ast.DictComp)):
                    self._emit(
                        mod, value.lineno, "static-unhashable",
                        f"{fn.name}({name}=...)",
                        f"static arg `{name}` of jitted `{fn.name}` bound "
                        f"to an unhashable "
                        f"{type(value).__name__.lower().replace('comp', ' comprehension')} "
                        f"-> TypeError at trace time")


class _FunctionAnalyzer:
    """Single-function walk: propagates tracedness, reports findings,
    descends into resolvable callees that receive traced arguments."""

    def __init__(self, owner: JitBoundaryPass, mod: _Module,
                 fn: ast.FunctionDef, traced_params: Set[str],
                 local_defs: Optional[Dict[str, ast.FunctionDef]] = None) -> None:
        self.o = owner
        self.mod = mod
        self.fn = fn
        self.traced: Set[str] = set(traced_params)
        # closures defined in an enclosing scope remain callable here
        self.local_defs: Dict[str, ast.FunctionDef] = dict(local_defs or {})

    def run(self) -> None:
        # two passes so names assigned late but read in earlier loop
        # bodies still pick up tracedness
        for _ in range(2):
            for stmt in self.fn.body:
                self._stmt(stmt)

    # -- statements ------------------------------------------------------
    def _stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # closures are analysed at their call sites (with the real arg
            # tracedness) or, when passed as callbacks to scan/checkpoint
            # etc., with every parameter traced — see _call
            self.local_defs[node.name] = node
            return
        if isinstance(node, ast.Assign):
            t = self._expr(node.value)
            for tgt in node.targets:
                self._bind(tgt, t)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            self._bind(node.target, self._expr(node.value))
        elif isinstance(node, ast.AugAssign):
            t = self._expr(node.value) or self._expr(node.target)
            self._bind(node.target, t)
        elif isinstance(node, (ast.If, ast.While)):
            t = self._expr(node.test)
            if t and not self._exempt_test(node.test):
                self.o._emit(
                    self.mod, node.test.lineno, "traced-branch",
                    self.fn.name,
                    "Python `if`/`while` on a traced value inside jit "
                    "(use lax.cond/jnp.where, or hoist to a static arg)")
            for stmt in node.body + node.orelse:
                self._stmt(stmt)
        elif isinstance(node, ast.For):
            self._bind(node.target, self._expr(node.iter))
            for stmt in node.body + node.orelse:
                self._stmt(stmt)
        elif isinstance(node, (ast.With,)):
            for item in node.items:
                self._expr(item.context_expr)
            for stmt in node.body:
                self._stmt(stmt)
        elif isinstance(node, ast.Try):
            for stmt in node.body:
                self._stmt(stmt)
            for h in node.handlers:
                for stmt in h.body:
                    self._stmt(stmt)
            for stmt in node.orelse + node.finalbody:
                self._stmt(stmt)
        elif isinstance(node, ast.Return) and node.value is not None:
            self._expr(node.value)
        elif isinstance(node, ast.Expr):
            self._expr(node.value)
        elif isinstance(node, (ast.Assert,)):
            pass  # asserts on shapes are trace-time checks, fine
        elif isinstance(node, ast.Raise):
            pass

    def _bind(self, target: ast.expr, traced: bool) -> None:
        if isinstance(target, ast.Name):
            if traced:
                self.traced.add(target.id)
            else:
                self.traced.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, traced)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, traced)
        # subscript/attribute writes don't change name tracedness

    def _analyze_local(self, cdef: ast.FunctionDef,
                       traced_params: Optional[Set[str]]) -> None:
        """Analyse a closure.  ``traced_params=None`` = callback semantics
        (every parameter traced).  Closure variables inherit the enclosing
        scope's tracedness; sibling closures stay callable."""
        key = (self.mod.name, cdef.lineno)
        if key in self.o._stack:
            return
        params = {a.arg for a in cdef.args.args + cdef.args.kwonlyargs}
        if traced_params is None:
            traced_params = set(params)
        inherited = self.traced - params
        memo_key = (self.mod.name, cdef.lineno,
                    frozenset(traced_params | inherited))
        if memo_key in self.o._memo:
            return
        self.o._memo.add(memo_key)
        self.o._stack.add(key)
        try:
            sub = _FunctionAnalyzer(self.o, self.mod, cdef,
                                    traced_params | inherited,
                                    local_defs=self.local_defs)
            sub.run()
        finally:
            self.o._stack.discard(key)

    @staticmethod
    def _exempt_test(test: ast.expr) -> bool:
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                for op in test.ops):
            return True
        if isinstance(test, ast.BoolOp):
            return all(_FunctionAnalyzer._exempt_test(v) for v in test.values)
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return _FunctionAnalyzer._exempt_test(test.operand)
        return False

    # -- expressions -----------------------------------------------------
    def _expr(self, node: Optional[ast.expr]) -> bool:
        """Returns True if the expression's value is (possibly) traced,
        reporting findings encountered on the way."""
        if node is None or isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.traced
        if isinstance(node, ast.Attribute):
            base = self._expr(node.value)
            if node.attr in _STATIC_ATTRS:
                return False
            return base
        if isinstance(node, ast.Subscript):
            self._expr(node.slice)
            return self._expr(node.value)
        if isinstance(node, (ast.BinOp,)):
            return self._expr(node.left) | self._expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self._expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self._expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            t = self._expr(node.left)
            for c in node.comparators:
                t |= self._expr(c)
            # identity / pytree-membership tests on tracers produce static
            # Python bools (they inspect structure, not values)
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in node.ops):
                return False
            return t
        if isinstance(node, ast.IfExp):
            t = self._expr(node.test)
            return self._expr(node.body) | self._expr(node.orelse) | t
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self._expr(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self._expr(v) for v in list(node.keys) + list(node.values)
                       if v is not None)
        if isinstance(node, ast.Starred):
            return self._expr(node.value)
        if isinstance(node, ast.Lambda):
            return False
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            t = any(self._expr(g.iter) for g in node.generators)
            for g in node.generators:
                self._bind(g.target, t)
            return self._expr(node.elt) | t
        if isinstance(node, ast.DictComp):
            t = any(self._expr(g.iter) for g in node.generators)
            for g in node.generators:
                self._bind(g.target, t)
            return self._expr(node.key) | self._expr(node.value) | t
        if isinstance(node, ast.JoinedStr):
            return False
        if isinstance(node, ast.Call):
            return self._call(node)
        return False

    def _call(self, node: ast.Call) -> bool:
        args_traced = [self._expr(a) for a in node.args]
        kw_traced = {kw.arg: self._expr(kw.value) for kw in node.keywords}
        any_traced = any(args_traced) or any(kw_traced.values())
        func = node.func

        # a closure passed as a callback (lax.scan body, jax.checkpoint,
        # cond branch): its parameters are tracers
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name) and arg.id in self.local_defs:
                self._analyze_local(self.local_defs[arg.id], None)

        # direct call of a closure: map real arg tracedness onto params
        if isinstance(func, ast.Name) and func.id in self.local_defs:
            cdef = self.local_defs[func.id]
            params = [a.arg for a in cdef.args.args]
            traced_params = {params[i] for i, t in enumerate(args_traced)
                             if t and i < len(params)}
            traced_params |= {k for k, t in kw_traced.items() if t and k}
            self._analyze_local(cdef, traced_params)
            return any_traced

        # wall-clock reads are a host dependency no matter the args
        if (isinstance(func, ast.Attribute) and func.attr in _TIME_FUNCS
                and isinstance(func.value, ast.Name)
                and func.value.id == "time"):
            self.o._emit(self.mod, node.lineno, "host-sync", self.fn.name,
                         f"`time.{func.attr}()` inside jit scope traces to a "
                         f"constant (and forces nothing at run time)")
            return False

        if isinstance(func, ast.Attribute):
            # tracer.item() / tracer.tolist()
            if func.attr in ("item", "tolist") and self._expr(func.value):
                self.o._emit(self.mod, node.lineno, "host-sync", self.fn.name,
                             f"`.{func.attr}()` on a traced value blocks on "
                             f"device transfer (ConcretizationTypeError "
                             f"under jit)")
                return False
            # np.asarray / np.array on tracers
            if (isinstance(func.value, ast.Name) and func.value.id == "np"
                    and func.attr in ("asarray", "array") and any_traced):
                self.o._emit(self.mod, node.lineno, "host-sync", self.fn.name,
                             f"`np.{func.attr}` on a traced value pulls the "
                             f"tracer to host")
                return False
            # jnp./jax./lax. calls: fine, result traced
            base = func.value
            if isinstance(base, ast.Name) and base.id in _ARRAY_NS:
                return True
            if (isinstance(base, ast.Attribute)
                    and isinstance(base.value, ast.Name)
                    and base.value.id == "jax"):
                return not (base.attr == "sharding"
                            and func.attr in _STATIC_SHARDING_QUERIES)
            # method on a traced object (reshape/astype/at...) -> traced
            if self._expr(func.value):
                return True

        if isinstance(func, ast.Name):
            if func.id in _CAST_BUILTINS and any_traced:
                self.o._emit(self.mod, node.lineno, "host-sync", self.fn.name,
                             f"`{func.id}()` on a traced value forces "
                             f"concretization (ConcretizationTypeError "
                             f"under jit)")
                return False
            if func.id in _STATIC_BUILTINS:
                return False

        # descend into resolvable callees when they receive tracers
        resolved = self.o._resolve_callable(self.mod, func)
        if resolved is not None:
            cmod, cdef = resolved
            params = [a.arg for a in cdef.args.args]
            traced_params: Set[str] = set()
            for i, t in enumerate(args_traced):
                if t and i < len(params):
                    traced_params.add(params[i])
            for name, t in kw_traced.items():
                if t and name:
                    traced_params.add(name)
            if traced_params:
                self.o._analyze(cmod, cdef, traced_params)
            return any_traced
        return any_traced


def run(files: Dict[str, Path], root: Path) -> List[Finding]:
    return JitBoundaryPass(files, root).run()
