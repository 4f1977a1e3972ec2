"""Tracing-hazard linter: AST rules over jit- and pallas-reachable code.

The serving stack's recompile-free guarantees (one compiled slot tick
per structure, traced per-slot windows, scalar-prefetch kernel inputs)
are easy to break silently: a single Python ``int()`` on a traced value,
an ``np.*`` call inside a tick body, or a builder closing over a dynamic
value turns "zero recompiles" into "one recompile per tick" — or into a
``TracerBoolConversionError`` the first time an untested path runs under
jit.  This pass finds those hazards statically.

How traced scope is computed
----------------------------
1. **Roots.** A function is a traced root if it is (a) decorated with /
   wrapped in ``jax.jit`` (including ``functools.partial(jax.jit, ...)``
   decorators), (b) the kernel body of a ``pl.pallas_call`` (resolved
   through ``functools.partial``), (c) wrapped by ``custom_vmap`` /
   ``def_vmap``, or (d) defined inside a ``build_*`` / ``make_*``
   function — the repo-wide idiom for "returns a jit-able closure".
2. **Reachability.** Roots are closed over a project-wide call graph
   (names resolved through ``from repro.x import f`` / ``import
   repro.x as y`` aliases), so helpers like ``core.join.join_pairs``
   are analyzed in traced context even though they are plain functions.
3. **Taint.** Inside a *root*, positional parameters are traced values
   unless their name marks them static (keyword-only parameters — the
   kernel convention for specialization constants — and
   ``STATIC_PARAMS`` names like ``plan`` / ``rel`` / ``backend`` are
   never traced).  For *reachable* functions, parameter taint flows in
   from call sites, so e.g. ``_trel_chain(prev.ets.shape[1])`` — a
   static shape — does not taint the callee.  Taint dies at ``.shape``
   / ``.dtype`` / ``len()`` (static under jit) and propagates through
   assignments, tuple unpacking and arithmetic; ``zip()`` unpacking is
   tracked per-position so static flag tuples riding next to traced
   refs stay untainted.

Rules
-----
TRC101 error    Python ``int()``/``float()``/``bool()`` cast on a traced
                value (concretization error / silent host sync).
TRC102 error    ``np.*`` call on a traced value (host compute inside a
                traced computation; breaks jit and pallas lowering).
TRC103 error    Host sync on a traced value: ``.tolist()`` / ``.item()``
                / ``.block_until_ready()`` / ``jax.device_get``.
TRC104 error    Python control flow (``if`` / ``while`` / ternary /
                ``assert``) on a traced value (``x is None`` checks are
                exempt — identity, not value).
TRC105 warning  A ``build_*`` / ``make_*`` builder's inner traced
                function closes over a non-structural builder parameter
                — the value becomes a compile-time constant, so every
                distinct value recompiles (the exact bug class PR 2
                fixed by making ``window`` a runtime input).
TRC106 warning  ``jax.jit`` wrapping a ``build_*tick*`` product without
                ``donate_argnums`` — the tick threads its (large) state
                through every call, so not donating doubles steady-state
                table memory traffic.
TRC107 error    ``repro.obs`` span/metric emission (``.span`` /
                ``.record`` / ``.event`` / ``.observe`` / ``.inc`` /
                ``.next_tick``) inside a traced function — a host
                callback inside jit either fails to trace or silently
                runs once at trace time; all instrumentation must stay
                on the host side of the serve loop.  Only modules that
                import ``repro.obs`` are checked (the attribute names
                alone are too generic); the ``n_obs_sites`` census
                counts every emission site tree-wide either way.

Suppression: ``# analysis: ignore[TRC105]`` (or bare ``ignore``) on the
flagged line; severities and the baseline workflow are described in
``repro.analysis.findings``.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

from repro.analysis.findings import ERROR, WARNING, Finding

# Parameter names that are structural / static by convention everywhere
# in this repo: never treated as traced values, allowed as builder
# closures.  Keep sorted; additions need a matching idiom in src.
STATIC_PARAMS = frozenset({
    "self", "cls",
    # plan / spec structure
    "plan", "plans", "template_plan", "spec", "specs", "q", "query",
    # backend / mode switches
    "backend", "interpret", "jit", "donate", "extract_matches",
    # static shapes & capacities
    "capacity", "max_new", "max_out", "n_slots", "n_shards", "n_nodes",
    "n_bags", "size", "prefix_depth",
    # kernel specialization constants
    "rel", "trel", "has_window", "tile_a", "tile_b", "tile_n", "tile_e",
    "batched", "acc_dtype", "axis_name", "axis_size", "in_batched",
    # model / training configs (hashable static pytrees)
    "cfg", "ocfg", "config", "mesh", "microbatches",
})

_BUILDER_RE = re.compile(r"^(build|make)_")
_IGNORE_RE = re.compile(r"#\s*analysis:\s*ignore(?:\[([A-Za-z0-9_,\s]+)\])?")
_KILL_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "nbytes"})
_KILL_CALLS = frozenset({"len", "range", "isinstance", "type", "repr",
                         "str", "enumerate"})
_CAST_CALLS = frozenset({"int", "float", "bool"})
_SYNC_ATTRS = frozenset({"tolist", "item", "block_until_ready"})
# repro.obs emission attributes (TRC107 + the n_obs_sites census).
# ``.set`` is deliberately excluded: too generic an attribute name to
# attribute to the obs layer from syntax alone.
_OBS_EMIT_ATTRS = frozenset({"span", "record", "event", "next_tick",
                             "observe", "inc", "set_total"})


@dataclass
class FuncInfo:
    """One analyzed function definition."""

    module: str                 # dotted module ("repro.core.engine")
    path: str                   # repo-relative file path
    qualname: str               # dotted within module ("build_tick.<tick>")
    node: ast.AST               # FunctionDef / AsyncFunctionDef
    parent: "FuncInfo | None"
    in_class: bool
    pos_params: tuple[str, ...]      # positional (incl. pos-or-kw + vararg)
    kwonly_params: tuple[str, ...]
    traced_root: bool = False
    seeded: bool = False        # positional params seeded as traced values
    shard_map_root: bool = False   # handed to a shard_map wrapper call
    traced: bool = False
    tainted_params: set[str] = field(default_factory=set)
    calls: list[tuple[str, ast.Call]] = field(default_factory=list)


@dataclass
class ModuleInfo:
    module: str
    path: str
    tree: ast.Module
    lines: list[str]
    # alias -> ("module", dotted) | ("func", (module, name))
    imports: dict[str, tuple] = field(default_factory=dict)
    functions: dict[str, FuncInfo] = field(default_factory=dict)  # qualname
    top_level: dict[str, FuncInfo] = field(default_factory=dict)  # name


# --------------------------------------------------------------------- #
# Collection
# --------------------------------------------------------------------- #
def _module_name(parent: str, path: str) -> str:
    """Dotted module for ``path`` relative to the dir containing the
    package root (src/repro/core/engine.py -> repro.core.engine)."""
    rel = os.path.relpath(path, parent).replace(os.sep, "/")
    parts = rel[:-3].split("/")            # strip .py
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _collect_imports(tree: ast.Module) -> dict[str, tuple]:
    out: dict[str, tuple] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    "module", a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = ("from", (node.module, a.name))
    return out


def _params(node) -> tuple[tuple[str, ...], tuple[str, ...]]:
    a = node.args
    pos = [p.arg for p in a.posonlyargs + a.args]
    if a.vararg:
        pos.append(a.vararg.arg)
    kw = [p.arg for p in a.kwonlyargs]
    return tuple(pos), tuple(kw)


def _collect_functions(mi: ModuleInfo) -> None:
    def visit(node, parent: FuncInfo | None, in_class: bool, prefix: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                pos, kw = _params(child)
                fi = FuncInfo(module=mi.module, path=mi.path, qualname=qual,
                              node=child, parent=parent, in_class=in_class,
                              pos_params=pos, kwonly_params=kw)
                mi.functions[qual] = fi
                if parent is None and not in_class:
                    mi.top_level[child.name] = fi
                visit(child, fi, False, qual + ".")
            elif isinstance(child, ast.ClassDef):
                visit(child, parent, True, prefix + child.name + ".")
            else:
                visit(child, parent, in_class, prefix)

    visit(mi.tree, None, False, "")


def _resolves_to(mi: ModuleInfo, name: str, *targets: str) -> bool:
    """Does local alias ``name`` resolve to one of the given modules?"""
    ent = mi.imports.get(name)
    if ent is None:
        return name in targets
    if ent[0] == "module":
        top = ent[1].split(".")[0]
        return ent[1] in targets or top in targets
    mod, attr = ent[1]
    return f"{mod}.{attr}" in targets


def _is_numpy(mi: ModuleInfo, node: ast.expr) -> bool:
    return (isinstance(node, ast.Name)
            and _resolves_to(mi, node.id, "numpy", "np"))


def _is_jax_attr(mi: ModuleInfo, node: ast.expr, attr: str) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == attr
            and isinstance(node.value, ast.Name)
            and _resolves_to(mi, node.value.id, "jax"))


def _is_jit_expr(mi: ModuleInfo, node: ast.expr) -> bool:
    """``jax.jit`` / ``jit`` / ``functools.partial(jax.jit, ...)``."""
    if _is_jax_attr(mi, node, "jit"):
        return True
    if isinstance(node, ast.Name) and node.id == "jit":
        ent = mi.imports.get("jit")
        return bool(ent and ent[0] == "from" and ent[1][0] == "jax")
    if isinstance(node, ast.Call) and node.args:
        f = node.func
        is_partial = (isinstance(f, ast.Attribute) and f.attr == "partial") \
            or (isinstance(f, ast.Name) and f.id == "partial")
        return is_partial and _is_jit_expr(mi, node.args[0])
    return False


def _local_assign_value(fn_node, name: str) -> ast.expr | None:
    """Last simple ``name = <expr>`` assignment inside ``fn_node``."""
    found = None
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    found = node.value
    return found


def _resolve_callable_name(mi: ModuleInfo, scope, expr) -> str | None:
    """Resolve an expression to a local function qualname, looking
    through one level of ``functools.partial`` and local assignment."""
    if isinstance(expr, ast.Call):
        f = expr.func
        is_partial = (isinstance(f, ast.Attribute) and f.attr == "partial") \
            or (isinstance(f, ast.Name) and f.id == "partial")
        if is_partial and expr.args:
            return _resolve_callable_name(mi, scope, expr.args[0])
        return None
    if not isinstance(expr, ast.Name):
        return None
    # a function visible from this scope?
    for qual, fi in mi.functions.items():
        if qual.split(".")[-1] == expr.id:
            return qual
    val = _local_assign_value(scope, expr.id) if scope is not None else None
    if val is not None and not (isinstance(val, ast.Name)
                                and val.id == expr.id):
        return _resolve_callable_name(mi, scope, val)
    return None


# Attribute names that take a function and trace it (jax.vmap, lax.scan,
# pl.pallas_call, shard_map, custom batching, ...).
_TRACING_WRAPPERS = frozenset({
    "vmap", "pmap", "pallas_call", "scan", "while_loop", "fori_loop",
    "cond", "switch", "shard_map", "checkpoint", "remat",
    "custom_vmap", "grad", "value_and_grad",
})


def _own_returned_names(fn_node) -> set[str]:
    """Names appearing in ``return`` expressions of ``fn_node`` itself
    (nested function bodies excluded)."""
    out: set[str] = set()
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Return) and node.value is not None:
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Name):
                    out.add(sub.id)
        stack.extend(ast.iter_child_nodes(node))
    return out


def _mark_roots(mi: ModuleInfo) -> None:
    for fi in mi.functions.values():
        for dec in fi.node.decorator_list:
            if _is_jit_expr(mi, dec):
                fi.traced_root = fi.seeded = True
            if isinstance(dec, ast.Name) and dec.id == "custom_vmap":
                fi.traced_root = fi.seeded = True
            if isinstance(dec, ast.Attribute) and dec.attr in (
                    "custom_vmap", "def_vmap"):
                fi.traced_root = fi.seeded = True
            # @pl.when(cond) wrapper decorators inside kernel bodies
            if isinstance(dec, ast.Call) and isinstance(
                    dec.func, ast.Attribute) and dec.func.attr == "when":
                fi.traced_root = True
        # Nested inside a build_* / make_* builder: part of the traced
        # computation (checked), but positional params are only *seeded*
        # as traced values if the builder returns the closure (or hands
        # it to a tracing wrapper, handled below) — build-time helpers
        # like engine._trel_chain take static args from their call
        # sites instead.
        p = fi.parent
        while p is not None:
            if _BUILDER_RE.match(p.qualname.split(".")[-1]):
                fi.traced_root = True
                break
            p = p.parent
        if (fi.parent is not None and fi.traced_root and not fi.seeded
                and _BUILDER_RE.match(
                    fi.parent.qualname.split(".")[-1])
                and fi.node.name in _own_returned_names(fi.parent.node)):
            fi.seeded = True

    # functions handed to jax.jit(...) or a tracing wrapper call.  The
    # leading-underscore strip covers import aliases like
    # ``shard_map as _shard_map``: the aliased call must still mark its
    # payload as a traced root.
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        wrap_name = None
        if isinstance(f, ast.Attribute):
            wrap_name = f.attr.lstrip("_")
        elif isinstance(f, ast.Name):
            wrap_name = f.id.lstrip("_")
        is_wrap = _is_jit_expr(mi, f) or wrap_name in _TRACING_WRAPPERS
        if not is_wrap:
            continue
        scope = _enclosing_function_node(mi, node)
        for arg in node.args:
            qual = _resolve_callable_name(mi, scope, arg)
            if qual is not None and qual in mi.functions:
                fi = mi.functions[qual]
                fi.traced_root = fi.seeded = True
                if wrap_name == "shard_map":
                    fi.shard_map_root = True


def _enclosing_function_node(mi: ModuleInfo, target) -> ast.AST | None:
    best = None
    for fi in mi.functions.values():
        for sub in ast.walk(fi.node):
            if sub is target:
                if best is None or _span(fi.node) < _span(best):
                    best = fi.node
                break
    return best


def _span(fn_node) -> int:
    return (fn_node.end_lineno or fn_node.lineno) - fn_node.lineno


# --------------------------------------------------------------------- #
# Taint
# --------------------------------------------------------------------- #
class _Taint:
    """Intra-procedural taint over local names of one function."""

    def __init__(self, mi: ModuleInfo, fi: FuncInfo):
        self.mi = mi
        self.fi = fi
        self.names: set[str] = set(fi.tainted_params)

    def expr(self, node) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.names
        if isinstance(node, ast.Attribute):
            if node.attr in _KILL_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id in _KILL_CALLS:
                return False
            if isinstance(f, ast.Attribute) and f.attr in _KILL_ATTRS:
                return False
            args = list(node.args) + [k.value for k in node.keywords]
            return any(self.expr(a) for a in args) or self.expr(f)
        if isinstance(node, (ast.BinOp,)):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return self.expr(node.left) or any(
                self.expr(c) for c in node.comparators)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self.expr(node.body) or self.expr(node.orelse)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        if isinstance(node, (ast.GeneratorExp, ast.ListComp, ast.SetComp)):
            return self.expr(node.elt) or any(
                self.expr(g.iter) for g in node.generators)
        if isinstance(node, ast.DictComp):
            return (self.expr(node.key) or self.expr(node.value)
                    or any(self.expr(g.iter) for g in node.generators))
        return False

    def _bind_target(self, target, value_tainted: bool,
                     value: ast.expr | None = None) -> None:
        if isinstance(target, ast.Name):
            if value_tainted:
                self.names.add(target.id)
            return
        if isinstance(target, ast.Starred):
            self._bind_target(target.value, value_tainted)
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            # zip() unpacking keeps per-position taint: static flag
            # tuples riding next to traced refs must stay untainted
            if (value is not None and isinstance(value, ast.Call)
                    and isinstance(value.func, ast.Name)
                    and value.func.id == "zip"
                    and len(value.args) == len(target.elts)):
                for t, a in zip(target.elts, value.args):
                    self._bind_target(t, self.expr(a))
                return
            for t in target.elts:
                self._bind_target(t, value_tainted)

    def run(self) -> None:
        """Two passes over the body (fixpoint for loop-carried taint)."""
        for _ in range(2):
            for node in ast.walk(self.fi.node):
                if isinstance(node, ast.Assign):
                    t = self.expr(node.value)
                    for tgt in node.targets:
                        self._bind_target(tgt, t, node.value)
                elif isinstance(node, ast.AugAssign):
                    if self.expr(node.value) or self.expr(node.target):
                        self._bind_target(node.target, True)
                elif isinstance(node, ast.AnnAssign) and node.value:
                    self._bind_target(node.target, self.expr(node.value),
                                      node.value)
                elif isinstance(node, ast.For):
                    self._bind_target(node.target, self.expr(node.iter),
                                      node.iter)
                elif isinstance(node, ast.comprehension):
                    self._bind_target(node.target, self.expr(node.iter),
                                      node.iter)
                elif isinstance(node, ast.NamedExpr):
                    self._bind_target(node.target, self.expr(node.value))


def _seed_root_taint(fi: FuncInfo) -> set[str]:
    return {p for p in fi.pos_params if p not in STATIC_PARAMS}


# --------------------------------------------------------------------- #
# Linter driver
# --------------------------------------------------------------------- #
class Linter:
    def __init__(self, root: str):
        self.root = root
        self.repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(root)))
        self.modules: dict[str, ModuleInfo] = {}
        self.findings: list[Finding] = []
        self.stats: dict = {}

    # ---------------- collection ---------------- #
    def load(self) -> None:
        for dirpath, _dirnames, filenames in sorted(os.walk(self.root)):
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path) as fh:
                    src = fh.read()
                mod = _module_name(os.path.dirname(os.path.abspath(
                    self.root)), path)
                rel = os.path.relpath(path, self.repo_root)
                mi = ModuleInfo(module=mod, path=rel,
                                tree=ast.parse(src, filename=path),
                                lines=src.splitlines())
                mi.imports = _collect_imports(mi.tree)
                _collect_functions(mi)
                self.modules[mod] = mi

    def _resolve_call(self, mi: ModuleInfo, fi: FuncInfo,
                      node: ast.Call) -> FuncInfo | None:
        """Resolve a call target to a project FuncInfo (best effort)."""
        f = node.func
        if isinstance(f, ast.Name):
            # sibling nested function or module top-level
            scope = fi
            while scope is not None:
                cand = f"{scope.qualname}.{f.id}"
                if cand in mi.functions:
                    return mi.functions[cand]
                scope = scope.parent
            if f.id in mi.top_level:
                return mi.top_level[f.id]
            ent = mi.imports.get(f.id)
            if ent and ent[0] == "from":
                src_mod, name = ent[1]
                smi = self.modules.get(src_mod)
                if smi and name in smi.top_level:
                    return smi.top_level[name]
        elif isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            ent = mi.imports.get(f.value.id)
            if ent and ent[0] == "module":
                smi = self.modules.get(ent[1])
                if smi and f.attr in smi.top_level:
                    return smi.top_level[f.attr]
            if ent and ent[0] == "from":
                smi = self.modules.get(f"{ent[1][0]}.{ent[1][1]}")
                if smi and f.attr in smi.top_level:
                    return smi.top_level[f.attr]
        return None

    def _propagate(self) -> None:
        """Close tracedness + parameter taint over the call graph."""
        infos = [fi for mi in self.modules.values()
                 for fi in mi.functions.values()]
        for fi in infos:
            if fi.traced_root:
                fi.traced = True
                if fi.seeded:
                    fi.tainted_params = _seed_root_taint(fi)
        for _ in range(12):                      # small fixpoint
            changed = False
            for mi in self.modules.values():
                for fi in mi.functions.values():
                    # a def nested in traced scope is itself traced
                    if (not fi.traced and fi.parent is not None
                            and fi.parent.traced):
                        fi.traced = True
                        changed = True
                    if not fi.traced:
                        continue
                    taint = _Taint(mi, fi)
                    taint.run()
                    for node in ast.walk(fi.node):
                        if not isinstance(node, ast.Call):
                            continue
                        callee = self._resolve_call(mi, fi, node)
                        if callee is None or callee is fi:
                            continue
                        if not callee.traced:
                            callee.traced = True
                            changed = True
                        for i, a in enumerate(node.args):
                            if i >= len(callee.pos_params):
                                break
                            p = callee.pos_params[i]
                            if (p not in STATIC_PARAMS
                                    and p not in callee.tainted_params
                                    and taint.expr(a)):
                                callee.tainted_params.add(p)
                                changed = True
                        for kw in node.keywords:
                            if (kw.arg and kw.arg in callee.pos_params
                                    and kw.arg not in STATIC_PARAMS
                                    and kw.arg not in callee.tainted_params
                                    and taint.expr(kw.value)):
                                callee.tainted_params.add(kw.arg)
                                changed = True
            if not changed:
                break

    # ---------------- reporting ---------------- #
    def _ignored(self, mi: ModuleInfo, line: int, rule: str) -> bool:
        if not (1 <= line <= len(mi.lines)):
            return False
        m = _IGNORE_RE.search(mi.lines[line - 1])
        if not m:
            return False
        rules = m.group(1)
        if rules is None:
            return True
        return rule in {r.strip() for r in rules.split(",")}

    def _emit(self, mi: ModuleInfo, fi: FuncInfo, node, rule: str,
              severity: str, message: str) -> None:
        line = getattr(node, "lineno", fi.node.lineno)
        if self._ignored(mi, line, rule):
            return
        self.findings.append(Finding(
            pass_name="lint", rule=rule, severity=severity, path=mi.path,
            line=line, symbol=f"{mi.module}.{fi.qualname}", message=message))

    # ---------------- rules ---------------- #
    def _is_none_check(self, node) -> bool:
        """Trace-safe tests: identity (``x is None``) and string-key
        membership in a params dict (``"w3" in p`` checks keys, which
        are static structure under jit, not traced values)."""
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return True
            return (all(isinstance(op, (ast.In, ast.NotIn))
                        for op in node.ops)
                    and isinstance(node.left, ast.Constant)
                    and isinstance(node.left.value, str))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            return self._is_none_check(node.operand)
        if isinstance(node, ast.BoolOp):
            return all(self._is_none_check(v) for v in node.values)
        return False

    def _check_traced_fn(self, mi: ModuleInfo, fi: FuncInfo) -> None:
        taint = _Taint(mi, fi)
        taint.run()
        own_nested = {f.node for q, f in mi.functions.items()
                      if f.parent is fi}
        for node in ast.walk(fi.node):
            if node in own_nested:
                continue                 # nested defs are checked on their own
            if isinstance(node, ast.Call):
                f = node.func
                args = list(node.args) + [k.value for k in node.keywords]
                any_tainted = any(taint.expr(a) for a in args)
                if (isinstance(f, ast.Name) and f.id in _CAST_CALLS
                        and any_tainted):
                    self._emit(mi, fi, node, "TRC101", ERROR,
                               f"Python {f.id}() on a traced value "
                               f"(concretizes under jit; host sync)")
                elif (isinstance(f, ast.Attribute)
                        and _is_numpy(mi, f.value) and any_tainted):
                    self._emit(mi, fi, node, "TRC102", ERROR,
                               f"np.{f.attr}() on a traced value (host "
                               f"compute inside traced scope; use jnp)")
                elif (isinstance(f, ast.Attribute)
                        and f.attr in _SYNC_ATTRS and taint.expr(f.value)):
                    self._emit(mi, fi, node, "TRC103", ERROR,
                               f".{f.attr}() on a traced value "
                               f"(device->host sync inside traced scope)")
                elif _is_jax_attr(mi, f, "device_get") and any_tainted:
                    self._emit(mi, fi, node, "TRC103", ERROR,
                               "jax.device_get on a traced value "
                               "(device->host sync inside traced scope)")
            elif isinstance(node, (ast.If, ast.While)):
                test = node.test
                if taint.expr(test) and not self._is_none_check(test):
                    kw = "while" if isinstance(node, ast.While) else "if"
                    self._emit(mi, fi, node, "TRC104", ERROR,
                               f"Python `{kw}` on a traced value (use "
                               f"jnp.where / lax.cond; traced bools "
                               f"cannot branch)")
            elif isinstance(node, ast.IfExp):
                if taint.expr(node.test) and not self._is_none_check(
                        node.test):
                    self._emit(mi, fi, node, "TRC104", ERROR,
                               "ternary on a traced value (use jnp.where)")
            elif isinstance(node, ast.Assert):
                if taint.expr(node.test) and not self._is_none_check(
                        node.test):
                    self._emit(mi, fi, node, "TRC104", ERROR,
                               "assert on a traced value (checkify or drop)")

    def _check_builder_closures(self, mi: ModuleInfo, fi: FuncInfo) -> None:
        """TRC105: inner traced fns closing over dynamic builder params."""
        if not _BUILDER_RE.match(fi.qualname.split(".")[-1]):
            return
        builder_params = [p for p in fi.pos_params + fi.kwonly_params
                          if p not in STATIC_PARAMS]
        if not builder_params:
            return
        inner = [f for f in mi.functions.values()
                 if f.parent is fi and f.traced]
        for child in inner:
            bound = set(child.pos_params) | set(child.kwonly_params)
            for sub in ast.walk(child.node):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and sub is not child.node:
                    bound |= {a.arg for a in sub.args.args}
                if isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        if isinstance(t, ast.Name):
                            bound.add(t.id)
            for sub in ast.walk(child.node):
                if (isinstance(sub, ast.Name)
                        and isinstance(sub.ctx, ast.Load)
                        and sub.id in builder_params
                        and sub.id not in bound):
                    self._emit(
                        mi, child, sub, "TRC105", WARNING,
                        f"traced closure captures builder parameter "
                        f"'{sub.id}' as a compile-time constant — every "
                        f"distinct value recompiles; make it a runtime "
                        f"input (cf. the PR-2 traced-window fix)")
                    break                         # one finding per capture

    @staticmethod
    def _imports_obs(mi: ModuleInfo) -> bool:
        for ent in mi.imports.values():
            if ent[0] == "module" and str(ent[1]).startswith("repro.obs"):
                return True
            if ent[0] == "from" and str(ent[1][0]).startswith("repro.obs"):
                return True
        return False

    def _check_obs_sites(self, mi: ModuleInfo) -> int:
        """TRC107 + census: ``repro.obs`` span/metric emission sites.

        Only modules importing ``repro.obs`` are scanned (the emission
        attribute names are too generic to attribute otherwise).
        Returns the module's site count; sites inside a TRACED function
        are host-callback-in-jit hazards and error."""
        if not self._imports_obs(mi):
            return 0
        n_sites = 0
        for fi in mi.functions.values():
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in _OBS_EMIT_ATTRS):
                    continue
                n_sites += 1
                if fi.traced:
                    self._emit(
                        mi, fi, node, "TRC107", ERROR,
                        f"obs emission .{node.func.attr}() reachable "
                        f"from a traced root — host callbacks inside "
                        f"jit fail to trace or fire once at trace "
                        f"time; hoist instrumentation out of the "
                        f"traced computation")
        return n_sites

    def _check_jit_donation(self, mi: ModuleInfo) -> None:
        """TRC106: jax.jit over a build_*tick* product, no donate_argnums."""
        for fi in mi.functions.values():
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Call)
                        and _is_jit_expr(mi, node.func) and node.args):
                    continue
                if any(k.arg == "donate_argnums" for k in node.keywords):
                    continue
                if self._wraps_tick(mi, fi.node, node.args[0]):
                    self._emit(
                        mi, fi, node, "TRC106", WARNING,
                        "jax.jit of a tick without donate_argnums: the "
                        "tick threads its full table state every call — "
                        "donate it (cf. SlotTickCache) or justify in the "
                        "baseline")

    def _wraps_tick(self, mi: ModuleInfo, scope, expr, depth: int = 0
                    ) -> bool:
        if depth > 4:
            return False
        if isinstance(expr, ast.Name):
            val = _local_assign_value(scope, expr.id)
            if val is not None:
                return self._wraps_tick(mi, scope, val, depth + 1)
            return False
        if isinstance(expr, ast.Call):
            f = expr.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else "")
            if re.search(r"(build_.*tick|tick_body)", name):
                return True
            return any(self._wraps_tick(mi, scope, a, depth + 1)
                       for a in expr.args)
        return False

    # ---------------- entry ---------------- #
    def run(self) -> list[Finding]:
        self.load()
        for mi in self.modules.values():
            _mark_roots(mi)
        self._propagate()
        n_obs_sites = 0
        for mi in self.modules.values():
            for fi in mi.functions.values():
                if fi.traced:
                    self._check_traced_fn(mi, fi)
                self._check_builder_closures(mi, fi)
            self._check_jit_donation(mi)
            n_obs_sites += self._check_obs_sites(mi)
        self.findings.sort(key=lambda f: (f.path, f.line, f.rule))
        n_traced = sum(1 for mi in self.modules.values()
                       for fi in mi.functions.values() if fi.traced)
        self.stats = {
            "n_files": len(self.modules),
            "n_functions": sum(len(mi.functions)
                               for mi in self.modules.values()),
            "n_traced_functions": n_traced,
            # tick bodies entering XLA through a shard_map wrapper (the
            # mesh/distributed entry points) — coverage census proving
            # the sharded builders stay under TRC checks
            "n_shard_map_roots": sum(
                1 for mi in self.modules.values()
                for fi in mi.functions.values() if fi.shard_map_root),
            # repro.obs span/metric emission sites in obs-importing
            # modules — all proven host-side (any one reachable from a
            # traced root is a TRC107 error above)
            "n_obs_sites": n_obs_sites,
        }
        return self.findings


def lint_tree(root: str) -> tuple[list[Finding], dict]:
    """Lint every module under ``root`` (a package dir like src/repro)."""
    linter = Linter(root)
    findings = linter.run()
    return findings, linter.stats
