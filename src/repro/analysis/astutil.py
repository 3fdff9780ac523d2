"""Shared AST machinery for the rule passes.

Three reusable layers:

* **Scope/import maps** — per-module parent links, import-alias
  normalization (``pl`` -> ``jax.experimental.pallas``), and a function
  index with lexical scope-chain lookup, so rules resolve ``Name`` call
  targets the way Python's own scoping does.
* **Jit-boundary inference** (:class:`TraceIndex`) — which functions in
  a module end up *traced*: direct entries (``jax.jit(f)``, decorator
  forms, ``lax.scan(step, ...)``, ``pallas_call(kernel, ...)``,
  ``shard_map``/``vmap``/``cond``/``while_loop``; ``functools.partial``
  indirection is followed), plus the transitive closure over
  locally-resolvable call edges, plus the explicit ``# repro: traced``
  source marker for closures handed across call boundaries the static
  call graph cannot follow.
* **Value taint** (:func:`taint_function`) — which local names of a
  traced function (transitively) derive from its traced positional
  parameters or from ``jnp``/``lax``/``pl`` results.  Keyword-only
  parameters are treated as static configuration (the idiom this
  codebase uses for ``functools.partial``-bound kernel scalars), as are
  ``static_argnames``/``static_argnums`` of a ``jax.jit`` entry.
  ``x is None`` checks, ``len()``/``isinstance()`` and
  ``.shape``/``.ndim``/``.dtype`` reads do not propagate taint (they
  yield Python values under tracing).  ``zip``/``enumerate`` loop
  targets are tainted element-wise so mixed static/traced iteration
  does not smear.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

FuncDef = (ast.FunctionDef, ast.AsyncFunctionDef)

# call-entry table: last dotted component -> positional indices of the
# function-valued arguments it traces
ENTRY_ARG_POSITIONS: Dict[str, Tuple[int, ...]] = {
    "jit": (0,), "vmap": (0,), "pmap": (0,), "grad": (0,),
    "value_and_grad": (0,), "checkpoint": (0,), "remat": (0,),
    "scan": (0,), "pallas_call": (0,), "shard_map": (0,),
    "while_loop": (0, 1), "fori_loop": (2,), "cond": (1, 2),
    "custom_vjp": (0,), "custom_jvp": (0,),
}
# dotted prefixes that mark a callable as "traces its argument" — a bare
# last-component match alone is not enough for common words like "scan"
_JAXISH_ROOTS = ("jax", "jax.numpy", "jax.lax", "jax.experimental",
                 "functools.partial")
# last components accepted even without a jax-ish root (their names are
# unambiguous in this codebase)
_ALWAYS_ENTRY = {"pallas_call", "shard_map"}

# namespaces whose call results are traced values
TRACER_ROOTS = ("jax", "jax.numpy", "jax.lax", "jax.experimental")

# attribute reads that yield static Python values even on tracers
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}
# positional parameters treated as static configuration by name — this
# codebase threads config objects/selectors positionally (cfg, opts,
# plan) and they are never traced values
STATIC_PARAM_NAMES = {"self", "cls", "cfg", "config", "opts", "options",
                      "plan", "spec", "mode", "kind", "backend", "name"}
# annotations that mark a parameter as a static Python value
_STATIC_ANNOTATION_NAMES = {"int", "float", "bool", "str", "bytes",
                            "complex"}
_STATIC_ANNOTATION_SUFFIXES = ("Config", "Options", "Spec", "Plan",
                               "Policy")
# builtins whose results are static Python values under tracing
_STATIC_CALLS = {"len", "isinstance", "issubclass", "getattr", "hasattr",
                 "type", "id", "repr", "str", "format", "range", "max",
                 "min", "sorted", "tuple", "list", "dict", "set", "zip",
                 "enumerate"}
# NOTE: max/min on tracers DO leak, but the leak surfaces as the flagged
# comparison/branch downstream; treating them static here avoids
# tainting `max(ci, 1)`-style config arithmetic.  bool/int/float are
# deliberately NOT here — they are the flagged coercions.


def parse_module(source: str, filename: str = "<module>") -> ast.Module:
    return ast.parse(source, filename=filename)


def build_parent_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ImportMap:
    """alias -> fully dotted origin (``pl`` ->
    ``jax.experimental.pallas``, ``_smap`` -> ``jax.shard_map``,
    ``np`` -> ``numpy``)."""

    def __init__(self, tree: ast.Module):
        self.alias: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.alias[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.alias[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def normalize(self, dotted: Optional[str]) -> Optional[str]:
        """Rewrite the leading alias of a dotted path to its origin."""
        if not dotted:
            return dotted
        head, _, rest = dotted.partition(".")
        origin = self.alias.get(head)
        if origin is None:
            return dotted
        return f"{origin}.{rest}" if rest else origin


@dataclass(eq=False)            # identity semantics: usable as dict key
class FunctionRecord:
    node: ast.AST                       # FunctionDef / AsyncFunctionDef
    qualname: str
    parent: Optional["FunctionRecord"]  # lexically enclosing function
    children: Dict[str, "FunctionRecord"] = field(default_factory=dict)

    @property
    def name(self) -> str:
        return self.node.name

    @property
    def lineno(self) -> int:
        return self.node.lineno

    def positional_params(self) -> List[str]:
        a = self.node.args
        names = [p.arg for p in a.posonlyargs + a.args]
        if a.vararg:
            names.append(a.vararg.arg)
        return names

    def kwonly_params(self) -> List[str]:
        a = self.node.args
        names = [p.arg for p in a.kwonlyargs]
        if a.kwarg:
            names.append(a.kwarg.arg)
        return names

    def all_params(self) -> List[str]:
        return self.positional_params() + self.kwonly_params()


class FunctionIndex:
    """Every function def in a module, with lexical scope-chain lookup."""

    def __init__(self, tree: ast.Module):
        self.records: List[FunctionRecord] = []
        self.module_scope: Dict[str, FunctionRecord] = {}
        self._by_node: Dict[ast.AST, FunctionRecord] = {}
        self._collect(tree, parent=None, prefix="")

    def _collect(self, node: ast.AST, parent: Optional[FunctionRecord],
                 prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, FuncDef):
                qual = f"{prefix}{child.name}"
                rec = FunctionRecord(child, qual, parent)
                self.records.append(rec)
                self._by_node[child] = rec
                if parent is None:
                    self.module_scope[child.name] = rec
                else:
                    parent.children[child.name] = rec
                self._collect(child, rec, prefix=f"{qual}.")
            elif isinstance(child, ast.ClassDef):
                self._collect(child, parent, prefix=f"{prefix}{child.name}.")
            else:
                self._collect(child, parent, prefix=prefix)

    def record_for(self, node: ast.AST) -> Optional[FunctionRecord]:
        return self._by_node.get(node)

    def lookup(self, scope: Optional[FunctionRecord],
               name: str) -> Optional[FunctionRecord]:
        """Resolve ``name`` as Python scoping would: the scope's own
        nested defs, then enclosing functions' defs, then module defs."""
        cur = scope
        while cur is not None:
            if name in cur.children:
                return cur.children[name]
            cur = cur.parent
        return self.module_scope.get(name)


def _static_argnames(call: ast.Call) -> Set[str]:
    """static_argnames= of a jit call (string / tuple-of-strings)."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                out.update(e.value for e in v.elts
                           if isinstance(e, ast.Constant)
                           and isinstance(e.value, str))
    return out


def _static_argnums(call: ast.Call) -> Set[int]:
    out: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                out.update(e.value for e in v.elts
                           if isinstance(e, ast.Constant)
                           and isinstance(e.value, int))
    return out


@dataclass
class TraceInfo:
    kind: str                     # "jit"|"scan"|"pallas_call"|...|"called"|"marker"
    origin_line: int              # where the entry/edge was seen
    static_names: Set[str] = field(default_factory=set)
    via: str = ""                 # human-readable provenance


class TraceIndex:
    """Which functions of a module are traced, and how."""

    def __init__(self, tree: ast.Module, imports: ImportMap,
                 funcindex: FunctionIndex, source_lines: Sequence[str]):
        self.traced: Dict[FunctionRecord, TraceInfo] = {}  # repro: noqa[RPR003] result map bounded by the module's function count, built once per parse
        self._tree = tree
        self._imports = imports
        self._index = funcindex
        self._parents = build_parent_map(tree)
        self._lines = source_lines
        self._find_direct_entries()
        self._find_markers()
        self._close_over_calls()

    # ---------------------------------------------------------- helpers
    def _entry_kind(self, callee: Optional[str]) -> Optional[str]:
        """'jit'/'scan'/... when the callee traces its fn arguments."""
        if not callee:
            return None
        last = callee.rsplit(".", 1)[-1]
        if last not in ENTRY_ARG_POSITIONS:
            return None
        if last in _ALWAYS_ENTRY:
            return last
        if any(callee == root or callee.startswith(root + ".")
               for root in _JAXISH_ROOTS) or callee == last:
            # bare `jit(f)` resolves through the import map to jax.jit;
            # an unnormalized bare name means a local helper — only
            # accept it when the import map mapped it (callee != last
            # after normalize) or it IS jax-ish.
            if callee == last and self._imports.normalize(last) == last:
                return None
            return last
        return None

    def _enclosing_function(self, node: ast.AST) -> Optional[FunctionRecord]:
        cur = self._parents.get(node)
        while cur is not None:
            rec = self._index.record_for(cur)
            if rec is not None:
                return rec
            cur = self._parents.get(cur)
        return None

    def _resolve_fn_arg(self, arg: ast.AST,
                        scope: Optional[FunctionRecord]
                        ) -> Optional[FunctionRecord]:
        """Resolve a function-valued argument: Name -> local def,
        following one level of ``x = functools.partial(f, ...)`` /
        ``x = f`` aliasing inside ``scope``."""
        if isinstance(arg, ast.Call):
            # partial(f, ...) / jax.jit(f) nested inline
            callee = self._imports.normalize(dotted_name(arg.func))
            if callee in ("functools.partial", "partial") or \
                    self._entry_kind(callee):
                if arg.args:
                    return self._resolve_fn_arg(arg.args[0], scope)
            return None
        if not isinstance(arg, ast.Name):
            return None
        rec = self._index.lookup(scope, arg.id)
        if rec is not None:
            return rec
        # alias assigned in the same scope: x = partial(f, ...) | x = f
        body_owner = scope.node if scope is not None else self._tree
        for stmt in ast.walk(body_owner):
            if isinstance(stmt, ast.Assign) and \
                    any(isinstance(t, ast.Name) and t.id == arg.id
                        for t in stmt.targets):
                v = stmt.value
                if isinstance(v, ast.Call):
                    callee = self._imports.normalize(dotted_name(v.func))
                    if callee in ("functools.partial", "partial") and v.args:
                        return self._resolve_fn_arg(v.args[0], scope)
                elif isinstance(v, ast.Name):
                    return self._index.lookup(scope, v.id)
        return None

    def _mark(self, rec: FunctionRecord, info: TraceInfo) -> None:
        if rec not in self.traced:
            self.traced[rec] = info

    # ----------------------------------------------------- entry finding
    def _find_direct_entries(self) -> None:
        # decorator forms
        for rec in self._index.records:
            for dec in rec.node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                callee = self._imports.normalize(dotted_name(target))
                kind = self._entry_kind(callee)
                if callee in ("functools.partial", "partial") and \
                        isinstance(dec, ast.Call) and dec.args:
                    inner = self._imports.normalize(
                        dotted_name(dec.args[0]))
                    kind = self._entry_kind(inner)
                    if kind:
                        self._mark(rec, TraceInfo(
                            kind, dec.lineno,
                            static_names=_static_argnames(dec),
                            via=f"@partial({inner}, ...)"))
                    continue
                if kind:
                    statics = (_static_argnames(dec)
                               if isinstance(dec, ast.Call) else set())
                    if isinstance(dec, ast.Call):
                        pos = rec.positional_params()
                        statics |= {pos[i] for i in _static_argnums(dec)
                                    if i < len(pos)}
                    self._mark(rec, TraceInfo(kind, dec.lineno,
                                              static_names=statics,
                                              via=f"@{callee}"))
        # call forms: jit(f), lax.scan(step, ...), pallas_call(kernel)
        for node in ast.walk(self._tree):
            if not isinstance(node, ast.Call):
                continue
            callee = self._imports.normalize(dotted_name(node.func))
            kind = self._entry_kind(callee)
            if not kind:
                continue
            scope = self._enclosing_function(node)
            statics = _static_argnames(node)
            nums = _static_argnums(node)
            for pos in ENTRY_ARG_POSITIONS[kind]:
                if pos < len(node.args):
                    rec = self._resolve_fn_arg(node.args[pos], scope)
                    if rec is not None:
                        st = set(statics)
                        ppos = rec.positional_params()
                        st |= {ppos[i] for i in nums if i < len(ppos)}
                        self._mark(rec, TraceInfo(
                            kind, node.lineno, static_names=st,
                            via=f"{callee}({rec.name}, ...)"))

    def _find_markers(self) -> None:
        """Opt-in ``# repro: traced`` comment on a def line."""
        for rec in self._index.records:
            line = ""
            if 0 < rec.lineno <= len(self._lines):
                line = self._lines[rec.lineno - 1]
            if "#" in line and "repro: traced" in line.split("#", 1)[1]:
                self._mark(rec, TraceInfo("marker", rec.lineno,
                                          via="# repro: traced"))

    def _close_over_calls(self) -> None:
        """Transitively trace locally-resolvable callees of traced fns."""
        work = list(self.traced.items())
        while work:
            rec, info = work.pop()
            for node in ast.walk(rec.node):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name):
                    callee = self._index.lookup(rec, node.func.id)
                    if callee is not None and callee not in self.traced \
                            and callee is not rec:
                        sub = TraceInfo("called", node.lineno,
                                        via=f"called from {rec.name} "
                                            f"({info.kind})")
                        self.traced[callee] = sub
                        work.append((callee, sub))


# ---------------------------------------------------------------------------
# Taint
# ---------------------------------------------------------------------------


@dataclass
class TaintFlag:
    node: ast.AST
    reason: str                         # "branch"|"coerce"|"np-call"|"assert"
    detail: str


def _annotation_is_static(ann: Optional[ast.AST]) -> bool:
    """Annotated int/float/bool/str/... or *Config/*Options/... types
    are static Python values under tracing."""
    if ann is None:
        return False
    if isinstance(ann, ast.Subscript):        # Optional[int] etc.
        name = dotted_name(ann.value)
        if name and name.rsplit(".", 1)[-1] in ("Optional", "Union"):
            return _annotation_is_static(ann.slice)
        return False
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        last = ann.value.rsplit(".", 1)[-1]
    else:
        name = dotted_name(ann)
        if not name:
            return False
        last = name.rsplit(".", 1)[-1]
    return (last in _STATIC_ANNOTATION_NAMES
            or last.endswith(_STATIC_ANNOTATION_SUFFIXES))


def static_params(rec: FunctionRecord, info: TraceInfo) -> Set[str]:
    """Positional params NOT treated as traced: explicit static_arg*,
    config-by-name, and scalar/config-annotated parameters."""
    out = set(info.static_names) | STATIC_PARAM_NAMES
    a = rec.node.args
    for p in a.posonlyargs + a.args:
        if _annotation_is_static(p.annotation):
            out.add(p.arg)
    return out


class _TaintWalker:
    def __init__(self, rec: FunctionRecord, info: TraceInfo,
                 imports: ImportMap):
        self.rec = rec
        self.imports = imports
        statics = static_params(rec, info)
        self.tainted: Set[str] = set(
            p for p in rec.positional_params() if p not in statics)
        self.flags: List[TaintFlag] = []

    # -------------------------------------------------- expression taint
    def _call_is_tracer(self, callee: Optional[str]) -> bool:
        return bool(callee) and any(
            callee == root or callee.startswith(root + ".")
            for root in TRACER_ROOTS)

    def expr_tainted(self, e: Optional[ast.AST]) -> bool:
        if e is None or isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Name):
            return e.id in self.tainted
        if isinstance(e, ast.Attribute):
            if e.attr in _STATIC_ATTRS:
                return False
            return self.expr_tainted(e.value)
        if isinstance(e, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
                return False                  # `x is (not) None` — Python bool
            return (self.expr_tainted(e.left)
                    or any(self.expr_tainted(c) for c in e.comparators))
        if isinstance(e, ast.Call):
            callee = self.imports.normalize(dotted_name(e.func))
            if callee in _STATIC_CALLS:
                return False
            if self._call_is_tracer(callee):
                return True
            return (self.expr_tainted(e.func)
                    or any(self.expr_tainted(a) for a in e.args)
                    or any(self.expr_tainted(k.value) for k in e.keywords))
        if isinstance(e, ast.IfExp):
            return (self.expr_tainted(e.test) or self.expr_tainted(e.body)
                    or self.expr_tainted(e.orelse))
        if isinstance(e, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                          ast.DictComp)):
            for gen in e.generators:
                self._bind_loop_target(gen.target, gen.iter)
            parts = ([e.key, e.value] if isinstance(e, ast.DictComp)
                     else [e.elt])
            # element IfExp tests inside comprehensions are checked here
            for p in parts:
                self._scan_expr_for_flags(p)
            return any(self.expr_tainted(p) for p in parts)
        return any(self.expr_tainted(c) for c in ast.iter_child_nodes(e))

    # ------------------------------------------------------- assignment
    def _taint_target(self, target: ast.AST) -> None:
        if isinstance(target, ast.Name):
            self.tainted.add(target.id)
        elif isinstance(target, ast.Starred):
            self._taint_target(target.value)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._taint_target(el)
        elif isinstance(target, (ast.Attribute, ast.Subscript)):
            # storing into x[...] / x.attr taints the container name
            root = target
            while isinstance(root, (ast.Attribute, ast.Subscript)):
                root = root.value
            if isinstance(root, ast.Name) and root.id != "self":
                self.tainted.add(root.id)

    def _bind_loop_target(self, target: ast.AST, it: ast.AST) -> None:
        """zip/enumerate-aware element-wise loop-target tainting."""
        callee = self.imports.normalize(dotted_name(it.func)) \
            if isinstance(it, ast.Call) else None
        if callee == "zip" and isinstance(target, (ast.Tuple, ast.List)) \
                and isinstance(it, ast.Call) \
                and len(it.args) == len(target.elts):
            for el, arg in zip(target.elts, it.args):
                if self.expr_tainted(arg):
                    self._taint_target(el)
            return
        if callee == "enumerate" and isinstance(target,
                                                (ast.Tuple, ast.List)) \
                and isinstance(it, ast.Call) and it.args \
                and len(target.elts) == 2:
            if self.expr_tainted(it.args[0]):
                self._taint_target(target.elts[1])
            return
        if self.expr_tainted(it):
            self._taint_target(target)

    # ---------------------------------------------------------- flagging
    def _flag_call(self, call: ast.Call) -> None:
        callee = self.imports.normalize(dotted_name(call.func))
        if callee in ("bool", "int", "float", "complex") and call.args \
                and self.expr_tainted(call.args[0]):
            self.flags.append(TaintFlag(
                call, "coerce",
                f"{callee}() coerces a traced value to a Python scalar"))
            return
        if callee and (callee == "numpy" or callee.startswith("numpy.")):
            fn = callee.rsplit(".", 1)[-1]
            if fn not in ("issubdtype", "ndim", "result_type", "dtype",
                          "bool_", "float32", "float64", "int32",
                          "int64") and (
                    any(self.expr_tainted(a) for a in call.args)
                    or any(self.expr_tainted(k.value)
                           for k in call.keywords)):
                self.flags.append(TaintFlag(
                    call, "np-call",
                    f"np.{fn}() applied to a traced value materializes "
                    "the tracer host-side"))
            return
        if isinstance(call.func, ast.Attribute) and \
                call.func.attr in ("item", "tolist", "__bool__",
                                   "__float__") and \
                self.expr_tainted(call.func.value):
            self.flags.append(TaintFlag(
                call, "coerce",
                f".{call.func.attr}() forces a traced value to host"))

    def _scan_expr_for_flags(self, e: Optional[ast.AST]) -> None:
        if e is None:
            return
        for node in ast.walk(e):
            if isinstance(node, ast.Call):
                self._flag_call(node)
            elif isinstance(node, ast.IfExp) and \
                    self.expr_tainted(node.test):
                self.flags.append(TaintFlag(
                    node, "branch",
                    "conditional expression branches on a traced value "
                    "(use jnp.where / lax.select)"))

    # ------------------------------------------------------- statements
    def run(self) -> None:
        self._walk_body(self.rec.node.body)

    def _walk_body(self, body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            self._walk_stmt(stmt)

    def _walk_stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, FuncDef):
            return                       # nested defs analyzed separately
        if isinstance(stmt, ast.Assign):
            self._scan_expr_for_flags(stmt.value)
            if self.expr_tainted(stmt.value):
                if len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], (ast.Tuple, ast.List)) \
                        and isinstance(stmt.value, (ast.Tuple, ast.List)) \
                        and len(stmt.targets[0].elts) == \
                        len(stmt.value.elts):
                    for el, v in zip(stmt.targets[0].elts,
                                     stmt.value.elts):
                        if self.expr_tainted(v):
                            self._taint_target(el)
                else:
                    for t in stmt.targets:
                        self._taint_target(t)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            self._scan_expr_for_flags(stmt.value)
            src_tainted = self.expr_tainted(stmt.value)
            if isinstance(stmt, ast.AugAssign):
                src_tainted = src_tainted or self.expr_tainted(stmt.target)
            if src_tainted:
                self._taint_target(stmt.target)
        elif isinstance(stmt, ast.If):
            self._scan_expr_for_flags(stmt.test)
            if self.expr_tainted(stmt.test):
                self.flags.append(TaintFlag(
                    stmt, "branch",
                    "Python `if` on a traced value bakes one branch into "
                    "the trace (use jnp.where / lax.cond)"))
            self._walk_body(stmt.body)
            self._walk_body(stmt.orelse)
        elif isinstance(stmt, ast.While):
            self._scan_expr_for_flags(stmt.test)
            if self.expr_tainted(stmt.test):
                self.flags.append(TaintFlag(
                    stmt, "branch",
                    "Python `while` on a traced value cannot be traced "
                    "(use lax.while_loop)"))
            self._walk_body(stmt.body)
            self._walk_body(stmt.orelse)
        elif isinstance(stmt, ast.Assert):
            self._scan_expr_for_flags(stmt.test)
            if self.expr_tainted(stmt.test):
                self.flags.append(TaintFlag(
                    stmt, "assert",
                    "assert on a traced value forces host sync "
                    "(use checkify or move outside the traced region)"))
        elif isinstance(stmt, ast.For):
            self._scan_expr_for_flags(stmt.iter)
            self._bind_loop_target(stmt.target, stmt.iter)
            self._walk_body(stmt.body)
            self._walk_body(stmt.orelse)
        elif isinstance(stmt, ast.With):
            for item in stmt.items:
                self._scan_expr_for_flags(item.context_expr)
                if item.optional_vars is not None and \
                        self.expr_tainted(item.context_expr):
                    self._taint_target(item.optional_vars)
            self._walk_body(stmt.body)
        elif isinstance(stmt, (ast.Try,)):
            self._walk_body(stmt.body)
            for h in stmt.handlers:
                self._walk_body(h.body)
            self._walk_body(stmt.orelse)
            self._walk_body(stmt.finalbody)
        elif isinstance(stmt, (ast.Expr, ast.Return)):
            self._scan_expr_for_flags(stmt.value)
        elif isinstance(stmt, ast.Raise):
            pass
        else:
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    self._scan_expr_for_flags(child)


def taint_function(rec: FunctionRecord, info: TraceInfo,
                   imports: ImportMap) -> Tuple[Set[str], List[TaintFlag]]:
    """Taint a traced function; returns (tainted names, flags)."""
    w = _TaintWalker(rec, info, imports)
    w.run()
    return w.tainted, w.flags


# ---------------------------------------------------------------------------
# Free variables / derivation roots (RPR002, RPR005)
# ---------------------------------------------------------------------------


def bound_names(rec: FunctionRecord) -> Set[str]:
    """Names bound inside a function: params, assignments, loop targets,
    nested defs, imports, withitems, comprehension targets."""
    out: Set[str] = set(rec.all_params())
    for node in ast.walk(rec.node):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for leaf in ast.walk(t):
                    if isinstance(leaf, ast.Name):
                        out.add(leaf.id)
        elif isinstance(node, ast.For):
            for leaf in ast.walk(node.target):
                if isinstance(leaf, ast.Name):
                    out.add(leaf.id)
        elif isinstance(node, ast.comprehension):
            for leaf in ast.walk(node.target):
                if isinstance(leaf, ast.Name):
                    out.add(leaf.id)
        elif isinstance(node, FuncDef) and node is not rec.node:
            out.add(node.name)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            for leaf in ast.walk(node.optional_vars):
                if isinstance(leaf, ast.Name):
                    out.add(leaf.id)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                out.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, ast.ExceptHandler) and node.name:
            out.add(node.name)
    return out


def free_names(rec: FunctionRecord) -> Set[str]:
    """Name loads in a function body not bound within the function."""
    bound = bound_names(rec)
    frees: Set[str] = set()
    for node in ast.walk(rec.node):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                and node.id not in bound:
            frees.add(node.id)
    return frees


def assignments_of(func_node: ast.AST) -> Dict[str, List[ast.expr]]:
    """name -> list of RHS expressions assigned to it, shallow walk of
    one function body (nested defs excluded)."""
    out: Dict[str, List[ast.expr]] = {}

    def visit(body):
        for stmt in body:
            if isinstance(stmt, FuncDef):
                continue
            if isinstance(stmt, ast.Assign):
                # element-wise for `a, b = x, y` so a's derivation roots
                # do not smear into b's (matters for RPR002 coverage)
                if len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], (ast.Tuple, ast.List)) \
                        and isinstance(stmt.value, (ast.Tuple, ast.List)) \
                        and len(stmt.targets[0].elts) == \
                        len(stmt.value.elts):
                    for t, v in zip(stmt.targets[0].elts,
                                    stmt.value.elts):
                        for leaf in ast.walk(t):
                            if isinstance(leaf, ast.Name):
                                out.setdefault(leaf.id, []).append(v)
                    continue
                for t in stmt.targets:
                    for leaf in ast.walk(t):
                        if isinstance(leaf, ast.Name):
                            out.setdefault(leaf.id, []).append(stmt.value)
            elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)) and \
                    isinstance(stmt.target, ast.Name) and \
                    stmt.value is not None:
                out.setdefault(stmt.target.id, []).append(stmt.value)
            elif isinstance(stmt, ast.For):
                for leaf in ast.walk(stmt.target):
                    if isinstance(leaf, ast.Name):
                        out.setdefault(leaf.id, []).append(stmt.iter)
                visit(stmt.body)
                visit(stmt.orelse)
            elif isinstance(stmt, (ast.If, ast.While)):
                visit(stmt.body)
                visit(stmt.orelse)
            elif isinstance(stmt, ast.With):
                visit(stmt.body)
            elif isinstance(stmt, ast.Try):
                visit(stmt.body)
                for h in stmt.handlers:
                    visit(h.body)
                visit(stmt.orelse)
                visit(stmt.finalbody)

    visit(func_node.body)
    return out


def name_loads(e: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(e)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}
