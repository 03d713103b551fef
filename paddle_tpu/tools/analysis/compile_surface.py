"""graftprog: whole-program compile-surface analysis (analysis v4).

The engine's central discipline — the compiled program set stays
``{chunk} + O(log2) prefill buckets + ONE decode + 1 gather + 1
scatter`` per device plane — was until now enforced only dynamically,
by trace counters inside tests.  graftprog proves it statically:

  1. **entry points** — modules register compile-surface roots via the
     ``__compile_surface_roots__`` dunder, the ``@compile_surface_root``
     decorator, or the central table (:mod:`.entrypoints`).  A class
     root seeds every method.
  2. **unit discovery** — every ``jax.jit`` (decorator, wrapper,
     partial, and factory forms like ``self._fn = self._build()``),
     ``shard_map``, ``pallas_call``, and jax.export AOT call in the
     project is a :class:`CompileUnit`, with its trace-counter tick
     (``X.trace_counts["name"] += 1`` inside the traced body), donation
     spec, holder attributes, and memoization idiom extracted from the
     AST.
  3. **reachability** — a BFS over the PR-4 project index, widened with
     function-local imports, bare name references (``defvjp`` halves,
     pallas kernel args), ``self.attr.method`` edges through inferred
     attribute types, and class-instantiation edges, maps every unit to
     the roots that reach it.  Units no root reaches are *dead
     programs*.
  4. **static keys** — each jit argument is classified **bucketed**
     (derives from a bucket producer: ``bucket_length``/``chunk_plan``/
     ``Scheduler.bucket`` — a finite key set), **trace-static** (shape
     fixed per config), or **unbounded** (a graftshape ``DYN`` extent
     inside the traced body, or a data-dependent Python value —
     ``int(x.sum())``, ``.item()`` — feeding a static jit arg).

``build_manifest`` emits the deterministic JSON program manifest
(``scripts/graftlint.py --manifest``): the per-entry-point program list
with key spaces and upper-bound counts that ROADMAP direction 2's AOT
exporter consumes, plus per-plane counter groups whose bounds ARE the
compile pin.  The ``compile-surface`` rule
(:mod:`.checkers.compile_surface`) turns the same facts into findings.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .checkers.base import (JIT_NAMES, PARTIAL_NAMES, _partial_of_jit,
                            assigned_names, dotted_name, param_names,
                            static_params)
from .entrypoints import (MARKER_NAMES, ROOTS_DUNDER,
                          registered_entry_points)
from .project import (ClassInfo, FunctionInfo, ModuleInfo, Project,
                      _resolve_relative, build_project)

__all__ = ["CompileUnit", "Surface", "build_surface", "surface_for",
           "build_manifest", "build_manifest_for_paths",
           "BUCKET_PRODUCERS", "BUILD_COUNT"]

# local functions whose RESULT is a shape bucket: values flowing out of
# them (through locals, tuple unpacks, constructor fields, np/jnp
# wrappers) give a jit argument a FINITE key set — the legal alternative
# to an unbounded per-value key
BUCKET_PRODUCERS = {"bucket_length", "chunk_plan", "bucket"}

# leaf names of the jax.export AOT entry points; matched only when the
# receiver resolves through the import table to an export-ish module
_AOT_LEAFS = {"export", "deserialize"}

# incremented on every build_surface() — the observable the perf/skip
# tests key on (a lint of files that cannot hold compile units must
# never pay for surface construction)
BUILD_COUNT = 0

_MAX_BUILDER_DEPTH = 3


@dataclass
class CompileUnit:
    """One statically-enumerated compilation: a jit/shard_map/
    pallas_call/AOT-export site plus everything the manifest needs."""
    uid: str
    kind: str                     # "jit" | "shard_map" | "pallas_call"
    #                             # | "aot-export"
    module: str
    relpath: str
    line: int
    col: int
    name: str                     # program name (inner fn / target text)
    owner: Optional[str] = None   # qname of the enclosing project fn
    inner: Optional[ast.AST] = None
    call: Optional[ast.AST] = None
    counter: Optional[str] = None  # trace_counts key ticked when traced
    donate: Tuple[int, ...] = ()
    static_args: Tuple[str, ...] = ()
    static_positions: Tuple[int, ...] = ()
    holders: Tuple[str, ...] = ()  # attributes/locals the program lives in
    memoized: bool = False
    in_loop: bool = False
    key_class: str = "trace-static"  # | "bucketed" | "unbounded"
    key_legs: Tuple[str, ...] = ()
    evidence: Optional[str] = None   # why unbounded, when it is
    roots: Tuple[str, ...] = ()      # entry points that reach this unit

    @property
    def upper_bound(self) -> str:
        if self.key_class == "unbounded":
            return "unbounded"
        if self.key_class == "bucketed":
            return "O(log2) shape buckets"
        return "1"

    def to_json(self) -> Dict:
        return {
            "id": self.uid, "kind": self.kind, "module": self.module,
            "path": self.relpath, "line": self.line, "name": self.name,
            "owner": self.owner, "counter": self.counter,
            "donate": list(self.donate),
            "static_args": sorted(self.static_args),
            "holders": sorted(self.holders), "memoized": self.memoized,
            "in_loop": self.in_loop,
            "key": {"class": self.key_class,
                    "legs": sorted(self.key_legs),
                    "upper_bound": self.upper_bound},
            "roots": sorted(self.roots),
        }


@dataclass
class Surface:
    """The computed compile surface of one project."""
    project: Project
    units: List[CompileUnit] = field(default_factory=list)
    roots: Dict[str, str] = field(default_factory=dict)  # qname -> how
    # root qname -> manifest plane group (class qname for class roots,
    # the root's own qname for plain function roots)
    root_groups: Dict[str, str] = field(default_factory=dict)
    # qname of fn -> set of root qnames that reach it
    reached: Dict[str, Set[str]] = field(default_factory=dict)
    # modules with at least one root/reached fn — participation gate for
    # the dead-program warning (a module outside the registered surface
    # is library code, not a dead program)
    active_modules: Set[str] = field(default_factory=set)

    def units_for(self, relpath: str) -> List[CompileUnit]:
        return [u for u in self.units if u.relpath == relpath]


# ----------------------------------------------------------- resolution

def _fn_local_imports(mod: ModuleInfo, fn: ast.AST) -> Dict[str, str]:
    """alias -> dotted target for imports INSIDE a function body — the
    module index only records top-level imports, but the serving stack
    leans on deferred ``from . import tp as _tp`` style imports."""
    out: Dict[str, str] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module if node.level == 0 else \
                _resolve_relative(mod, node.level, node.module)
            if base is None:
                continue
            for a in node.names:
                if a.name != "*":
                    out[a.asname or a.name] = f"{base}.{a.name}"
    return out


def _resolve_in_fn(project: Project, fi: FunctionInfo, dotted: str,
                   local_imports: Dict[str, str]) -> Optional[FunctionInfo]:
    """resolve_call widened with the function-local import table."""
    hit = project.resolve_call(fi.module, dotted, cls=fi.cls)
    if hit is not None:
        return hit
    parts = dotted.split(".")
    target = local_imports.get(parts[0])
    if target is not None:
        return project.resolve_qname(".".join([target] + parts[1:]))
    return None


def _annotation_leaf(ann: Optional[ast.AST]) -> Optional[str]:
    return Project._annotation_class_name(ann)


def _param_annotations(fi: FunctionInfo) -> Dict[str, str]:
    out: Dict[str, str] = {}
    a = fi.node.args
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        leaf = _annotation_leaf(p.annotation)
        if leaf:
            out[p.arg] = leaf
    return out


def _iter_functions(mod: ModuleInfo):
    yield from mod.functions.values()
    for c in mod.classes.values():
        yield from c.methods.values()


# -------------------------------------------------------- reachability

def _edge_set(project: Project, fi: FunctionInfo,
              cache: Dict[str, Tuple[str, ...]]) -> Tuple[str, ...]:
    hit = cache.get(fi.qname)
    if hit is not None:
        return hit
    mod = project.modules.get(fi.module)
    out: Set[str] = {c.qname for c in project.callees(fi)}
    local_imports = _fn_local_imports(mod, fi.node) if mod else {}
    ann = _param_annotations(fi)
    attr_types = project.class_attr_types(fi.module, fi.cls) \
        if fi.cls else {}
    own_cls = mod.classes.get(fi.cls) if (mod and fi.cls) else None
    for node in ast.walk(fi.node):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            # bare references: defvjp halves, pallas kernel args,
            # callbacks stuffed into registries
            ref = project.resolve_call(fi.module, node.id, cls=fi.cls)
            if ref is None and node.id in local_imports:
                ref = project.resolve_qname(local_imports[node.id])
            if ref is not None:
                out.add(ref.qname)
            continue
        if not isinstance(node, ast.Call):
            continue
        d = dotted_name(node.func)
        if d is None:
            continue
        parts = d.split(".")
        hit = _resolve_in_fn(project, fi, d, local_imports)
        if hit is not None:
            out.add(hit.qname)
        # class instantiation: C(...) / Cls.create handled by
        # resolve_call; the constructor edge needs the class lookup
        ci = project.resolve_class(fi.module, d)
        if ci is None and len(parts) == 1 and parts[0] in local_imports:
            tgt = local_imports[parts[0]]
            owner_mod = project._longest_module_prefix(tgt)
            if owner_mod and owner_mod != tgt:
                ci = project.modules[owner_mod].classes.get(
                    tgt[len(owner_mod) + 1:])
        if ci is None and d == "cls" and own_cls is not None:
            ci = own_cls
        if ci is not None:
            init = ci.methods.get("__init__")
            if init is not None:
                out.add(init.qname)
        # self.attr.method(...) through inferred attribute types
        if len(parts) == 3 and parts[0] in ("self", "cls"):
            for cand in attr_types.get(parts[1], ()):
                m = cand.methods.get(parts[2])
                if m is not None:
                    out.add(m.qname)
        # param.method(...) through the parameter annotation
        if len(parts) == 2 and parts[0] in ann:
            pc = project.resolve_class(fi.module, ann[parts[0]])
            if pc is not None:
                m = pc.methods.get(parts[1])
                if m is not None:
                    out.add(m.qname)
    out.discard(fi.qname)
    result = tuple(sorted(out))
    cache[fi.qname] = result
    return result


def _module_level_refs(project: Project, mod: ModuleInfo,
                       cache: Dict[str, Tuple[str, ...]]) -> Tuple[str, ...]:
    """Functions referenced by module TOP-LEVEL code (outside any def/
    class): custom_vjp constructions, ``defvjp`` registrations, registry
    dicts.  Module-level code runs on import, so these are reachable the
    moment anything in the module is."""
    hit = cache.get(mod.name)
    if hit is not None:
        return hit
    out: Set[str] = set()
    for stmt in mod.tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and isinstance(node.ctx,
                                                         ast.Load):
                ref = project.resolve_call(mod.name, node.id)
                if ref is not None:
                    out.add(ref.qname)
    result = tuple(sorted(out))
    cache[mod.name] = result
    return result


def _collect_roots(project: Project
                   ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """(qname -> registration mechanism, qname -> plane group),
    expanding class roots to every method (the class is the entry
    surface; any method may be the first thing a caller touches)."""
    roots: Dict[str, str] = {}
    groups: Dict[str, str] = {}

    def add_fn(fi: FunctionInfo, how: str,
               group: Optional[str] = None) -> None:
        roots.setdefault(fi.qname, how)
        groups.setdefault(fi.qname, group or fi.qname)

    def add_cls(ci: ClassInfo, how: str) -> None:
        group = f"{ci.module}.{ci.name}"
        for m in ci.methods.values():
            add_fn(m, how, group)

    for mod in project.modules.values():
        for stmt in mod.tree.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and stmt.targets[0].id == ROOTS_DUNDER \
                    and isinstance(stmt.value, (ast.Tuple, ast.List)):
                for elt in stmt.value.elts:
                    if not (isinstance(elt, ast.Constant)
                            and isinstance(elt.value, str)):
                        continue
                    if elt.value in mod.functions:
                        add_fn(mod.functions[elt.value], "marker")
                    elif elt.value in mod.classes:
                        add_cls(mod.classes[elt.value], "marker")
        for fi in _iter_functions(mod):
            for dec in fi.node.decorator_list:
                d = dotted_name(dec) or (
                    dotted_name(dec.func) if isinstance(dec, ast.Call)
                    else None)
                if d and d.split(".")[-1] in MARKER_NAMES:
                    add_fn(fi, "decorator")
        for ci in mod.classes.values():
            for dec in ci.node.decorator_list:
                d = dotted_name(dec) or (
                    dotted_name(dec.func) if isinstance(dec, ast.Call)
                    else None)
                if d and d.split(".")[-1] in MARKER_NAMES:
                    add_cls(ci, "decorator")
    for qname in registered_entry_points():
        fi = project.resolve_qname(qname)
        if fi is not None:
            add_fn(fi, "table")
            continue
        owner_mod = project._longest_module_prefix(qname)
        if owner_mod and owner_mod != qname:
            ci = project.modules[owner_mod].classes.get(
                qname[len(owner_mod) + 1:])
            if ci is not None:
                add_cls(ci, "table")
    return roots, groups


def _reach(project: Project, roots: Dict[str, str]
           ) -> Tuple[Dict[str, Set[str]], Set[str]]:
    edge_cache: Dict[str, Tuple[str, ...]] = {}
    ref_cache: Dict[str, Tuple[str, ...]] = {}
    by_qname = {fi.qname: fi for fi in project.all_functions()}
    reached: Dict[str, Set[str]] = {}
    active_modules: Set[str] = set()
    # modules whose top-level refs have been injected, per root
    seen_mod: Set[Tuple[str, str]] = set()

    for root in sorted(roots):
        stack = [root]
        while stack:
            q = stack.pop()
            fi = by_qname.get(q)
            if fi is None:
                continue
            got = reached.setdefault(q, set())
            if root in got:
                continue
            got.add(root)
            active_modules.add(fi.module)
            mkey = (fi.module, root)
            if mkey not in seen_mod:
                seen_mod.add(mkey)
                mod = project.modules.get(fi.module)
                if mod is not None:
                    stack.extend(_module_level_refs(project, mod,
                                                    ref_cache))
            stack.extend(_edge_set(project, fi, edge_cache))
    return reached, active_modules


# ----------------------------------------------------- unit discovery

def _parent_map(tree: ast.AST) -> Dict[int, ast.AST]:
    return {id(child): parent for parent in ast.walk(tree)
            for child in ast.iter_child_nodes(parent)}


def _enclosing(parents: Dict[int, ast.AST], node: ast.AST,
               kinds) -> Optional[ast.AST]:
    cur = parents.get(id(node))
    while cur is not None:
        if isinstance(cur, kinds):
            return cur
        cur = parents.get(id(cur))
    return None


def _owner_info(parents: Dict[int, ast.AST], node: ast.AST,
                node_to_fi: Dict[int, FunctionInfo]
                ) -> Optional[FunctionInfo]:
    cur = parents.get(id(node))
    while cur is not None:
        if id(cur) in node_to_fi:
            return node_to_fi[id(cur)]
        cur = parents.get(id(cur))
    return None


def _in_loop(parents: Dict[int, ast.AST], node: ast.AST,
             stop: Optional[ast.AST]) -> bool:
    cur = parents.get(id(node))
    while cur is not None and cur is not stop:
        if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
            return True
        cur = parents.get(id(cur))
    return False


def _find_local_def(scope: Optional[ast.AST], mod: ModuleInfo,
                    name: str) -> Optional[ast.AST]:
    if scope is not None:
        for n in ast.walk(scope):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n.name == name:
                return n
    fi = mod.functions.get(name)
    return fi.node if fi is not None else None


def _resolve_jit_target(expr: Optional[ast.AST], scope: Optional[ast.AST],
                        mod: ModuleInfo, depth: int = 0
                        ) -> Tuple[Optional[ast.AST], str]:
    """(inner FunctionDef-or-None, program name) for a jit/shard_map/
    pallas_call first argument — chasing Names to nested or module-level
    defs and unwrapping functools.partial layers."""
    if expr is None or depth > 3:
        return None, "<unknown>"
    if isinstance(expr, ast.Lambda):
        return None, "<lambda>"
    if isinstance(expr, ast.Name):
        hit = _find_local_def(scope, mod, expr.id)
        if hit is not None:
            return hit, expr.id
        # X = functools.partial(f, ...) in the same scope
        if scope is not None:
            for n in ast.walk(scope):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and isinstance(n.targets[0], ast.Name) \
                        and n.targets[0].id == expr.id \
                        and isinstance(n.value, ast.Call) \
                        and dotted_name(n.value.func) in PARTIAL_NAMES \
                        and n.value.args:
                    return _resolve_jit_target(n.value.args[0], scope,
                                               mod, depth + 1)
        return None, expr.id
    if isinstance(expr, ast.Call) \
            and dotted_name(expr.func) in PARTIAL_NAMES and expr.args:
        return _resolve_jit_target(expr.args[0], scope, mod, depth + 1)
    d = dotted_name(expr)
    return None, d or "<unknown>"


def _counter_of(inner: Optional[ast.AST]) -> Optional[str]:
    """The trace_counts key the traced body ticks — the static link
    between a compile unit and the runtime trace counter that verifies
    it (``X.trace_counts["name"] += 1`` is a trace-time side effect)."""
    if inner is None:
        return None
    for n in ast.walk(inner):
        if isinstance(n, ast.AugAssign) \
                and isinstance(n.target, ast.Subscript) \
                and isinstance(n.target.value, ast.Attribute) \
                and n.target.value.attr == "trace_counts" \
                and isinstance(n.target.slice, ast.Constant) \
                and isinstance(n.target.slice.value, str):
            return n.target.slice.value
    return None


def _donate_spec(call: Optional[ast.AST]) -> Tuple[int, ...]:
    if not isinstance(call, ast.Call):
        return ()
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            return tuple(n.value for n in ast.walk(kw.value)
                         if isinstance(n, ast.Constant)
                         and isinstance(n.value, int))
    return ()


def _static_positions(inner: Optional[ast.AST],
                      jit_call: Optional[ast.AST]
                      ) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    if not isinstance(jit_call, ast.Call):
        return (), ()
    positions: Set[int] = set()
    names: Set[str] = set()
    for kw in jit_call.keywords:
        if kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value,
                                                              int):
                    positions.add(n.value)
    if inner is not None:
        names = static_params(inner, jit_call)
        pos_params = [p.arg for p in
                      inner.args.posonlyargs + inner.args.args]
        for nm in names:
            if nm in pos_params:
                positions.add(pos_params.index(nm))
    return tuple(sorted(positions)), tuple(sorted(names))


# --------------------------------------------------- bucket-key taint

def _ctor_field_map(ci: ClassInfo) -> Tuple[List[str], Dict[str, str]]:
    """(positional field order, param->attr map) for a constructor call:
    ``__init__`` params (self-attr assignments resolve param to field),
    or declared-field order for ``__init__``-less dataclasses."""
    init = ci.methods.get("__init__")
    if init is not None:
        a = init.node.args
        params = [p.arg for p in a.posonlyargs + a.args][1:]
        p2f: Dict[str, str] = {}
        for n in ast.walk(init.node):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Attribute) \
                    and isinstance(n.targets[0].value, ast.Name) \
                    and n.targets[0].value.id == "self" \
                    and isinstance(n.value, ast.Name):
                p2f.setdefault(n.value.id, n.targets[0].attr)
        return params, p2f
    fields = [s.target.id for s in ci.node.body
              if isinstance(s, ast.AnnAssign)
              and isinstance(s.target, ast.Name)]
    return fields, {f: f for f in fields}


class _BucketTaint:
    """Per-module dataflow: which locals/fields derive from a bucket
    producer.  Two-phase so a plan computed in one method and consumed
    through a constructor field in another still classifies (the
    ``_Prefill.plan`` chain in the engine)."""

    def __init__(self, project: Project, mod: ModuleInfo):
        self.project = project
        self.mod = mod
        # ClassInfo key "module.Cls" -> tainted field names
        self.field_taints: Dict[str, Set[str]] = {}
        self.fn_taints: Dict[str, Set[str]] = {}
        for _ in range(2):
            for fi in _iter_functions(mod):
                self.fn_taints[fi.qname] = self._fn_pass(fi)

    def _cls_key(self, ci: Optional[ClassInfo]) -> Optional[str]:
        return f"{ci.module}.{ci.name}" if ci is not None else None

    def tainted_expr(self, node: ast.AST, fi: FunctionInfo,
                     tainted: Optional[Set[str]] = None) -> bool:
        if tainted is None:
            tainted = self.fn_taints.get(fi.qname, set())
        ann = _param_annotations(fi)
        own = self._cls_key(self.mod.classes.get(fi.cls)) if fi.cls \
            else None

        def rec(n: ast.AST) -> bool:
            if isinstance(n, ast.Name):
                return n.id in tainted
            if isinstance(n, ast.Attribute):
                if isinstance(n.value, ast.Name):
                    key = None
                    if n.value.id == "self":
                        key = own
                    elif n.value.id in ann:
                        key = self._cls_key(self.project.resolve_class(
                            fi.module, ann[n.value.id]))
                    if key is not None \
                            and n.attr in self.field_taints.get(key, ()):
                        return True
                return False
            if isinstance(n, ast.Subscript):
                return rec(n.value)
            if isinstance(n, ast.Call):
                d = dotted_name(n.func)
                if d is not None \
                        and d.split(".")[-1] in BUCKET_PRODUCERS:
                    return True
                args = list(n.args) + [k.value for k in n.keywords]
                return any(rec(a) for a in args)
            if isinstance(n, ast.BinOp):
                return rec(n.left) or rec(n.right)
            if isinstance(n, ast.UnaryOp):
                return rec(n.operand)
            if isinstance(n, (ast.Tuple, ast.List)):
                return any(rec(e) for e in n.elts)
            if isinstance(n, ast.Starred):
                return rec(n.value)
            if isinstance(n, ast.IfExp):
                return rec(n.body) or rec(n.orelse)
            return False

        return rec(node)

    def _fn_pass(self, fi: FunctionInfo) -> Set[str]:
        tainted: Set[str] = set()
        own_ci = self.mod.classes.get(fi.cls) if fi.cls else None
        for _ in range(2):
            for node in ast.walk(fi.node):
                if isinstance(node, ast.Assign):
                    if self.tainted_expr(node.value, fi, tainted):
                        for t in node.targets:
                            tainted.update(assigned_names(t))
                            if isinstance(t, ast.Attribute) \
                                    and isinstance(t.value, ast.Name) \
                                    and t.value.id == "self" \
                                    and own_ci is not None:
                                self.field_taints.setdefault(
                                    self._cls_key(own_ci),
                                    set()).add(t.attr)
                elif isinstance(node, ast.AnnAssign) \
                        and node.value is not None \
                        and isinstance(node.target, ast.Name) \
                        and self.tainted_expr(node.value, fi, tainted):
                    tainted.add(node.target.id)
        # constructor calls carrying tainted args taint the mapped field
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None:
                continue
            ci = self.project.resolve_class(fi.module, d)
            if ci is None and "." in d:
                ci = self.project.resolve_class(fi.module,
                                                d.rsplit(".", 1)[0])
            if ci is None:
                continue
            order, p2f = _ctor_field_map(ci)
            key = self._cls_key(ci)
            for i, a in enumerate(node.args):
                if i < len(order) \
                        and self.tainted_expr(a, fi, tainted):
                    f = p2f.get(order[i], order[i])
                    self.field_taints.setdefault(key, set()).add(f)
            for kw in node.keywords:
                if kw.arg is not None \
                        and self.tainted_expr(kw.value, fi, tainted):
                    f = p2f.get(kw.arg, kw.arg)
                    self.field_taints.setdefault(key, set()).add(f)
        return tainted


def _data_dependent(expr: ast.AST) -> bool:
    """A Python value feeding a jit key that varies per RUNTIME DATA:
    int()/float() of a non-literal, non-shape expression, or an
    ``.item()``/``.tolist()`` readback anywhere inside it."""
    for n in ast.walk(expr):
        if not isinstance(n, ast.Call):
            continue
        d = dotted_name(n.func)
        if d in ("int", "float") and n.args \
                and not isinstance(n.args[0], ast.Constant):
            shapeish = any(isinstance(x, ast.Attribute)
                           and x.attr in ("shape", "ndim", "size")
                           for x in ast.walk(n.args[0]))
            if not shapeish:
                return True
        if isinstance(n.func, ast.Attribute) \
                and n.func.attr in ("item", "tolist"):
            return True
    return False


# -------------------------------------------------------- the builder

def build_surface(project: Project) -> Surface:
    global BUILD_COUNT
    BUILD_COUNT += 1
    surface = Surface(project=project)
    surface.roots, surface.root_groups = _collect_roots(project)
    surface.reached, surface.active_modules = _reach(project,
                                                     surface.roots)

    node_to_fi: Dict[int, FunctionInfo] = {}
    for fi in project.all_functions():
        node_to_fi[id(fi.node)] = fi

    # global holder graph: callee qname -> [(fn, holder, is_attr)], and
    # fn qname -> [callee qnames it returns a call of] (builder chase)
    assign_edges: Dict[str, List[Tuple[FunctionInfo, str, bool]]] = {}
    return_edges: Dict[str, List[str]] = {}
    for fi in project.all_functions():
        mod = project.modules.get(fi.module)
        local_imports = _fn_local_imports(mod, fi.node) if mod else {}
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.value, ast.Call):
                d = dotted_name(node.value.func)
                if d is None:
                    continue
                hit = _resolve_in_fn(project, fi, d, local_imports)
                if hit is None:
                    continue
                t = node.targets[0]
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id in ("self", "cls"):
                    assign_edges.setdefault(hit.qname, []).append(
                        (fi, t.attr, True))
                elif isinstance(t, ast.Name):
                    assign_edges.setdefault(hit.qname, []).append(
                        (fi, t.id, False))
            elif isinstance(node, ast.Return) \
                    and isinstance(node.value, ast.Call):
                d = dotted_name(node.value.func)
                if d is None:
                    continue
                hit = _resolve_in_fn(project, fi, d, local_imports)
                if hit is not None:
                    return_edges.setdefault(hit.qname, []).append(
                        fi.qname)

    taints: Dict[str, _BucketTaint] = {}

    def taint_for(mod: ModuleInfo) -> _BucketTaint:
        bt = taints.get(mod.name)
        if bt is None:
            bt = _BucketTaint(project, mod)
            taints[mod.name] = bt
        return bt

    for mod in sorted(project.modules.values(), key=lambda m: m.relpath):
        _discover_units(project, mod, surface, node_to_fi)

    by_qname = {fi.qname: fi for fi in project.all_functions()}
    for unit in surface.units:
        _attach_holders(project, unit, assign_edges, return_edges,
                        by_qname)
        _classify_unit(project, unit, taint_for, by_qname)
        if unit.owner is not None:
            unit.roots = tuple(sorted(
                surface.reached.get(unit.owner, ())))
        elif unit.module in surface.active_modules:
            # module-level unit: alive with the module itself
            unit.roots = tuple(sorted({
                r for q, rs in surface.reached.items()
                for r in rs
                if by_qname.get(q) is not None
                and by_qname[q].module == unit.module}))
    surface.units.sort(key=lambda u: (u.relpath, u.line, u.col))
    return surface


def _discover_units(project: Project, mod: ModuleInfo, surface: Surface,
                    node_to_fi: Dict[int, FunctionInfo]) -> None:
    parents = _parent_map(mod.tree)
    seen_calls: Set[int] = set()

    def add(kind: str, node: ast.AST, inner: Optional[ast.AST],
            name: str, call: Optional[ast.AST],
            owner: Optional[FunctionInfo]) -> None:
        uid = f"{mod.name}:{node.lineno}:{kind}"
        spos, snames = _static_positions(inner, call)
        surface.units.append(CompileUnit(
            uid=uid, kind=kind, module=mod.name, relpath=mod.relpath,
            line=node.lineno, col=node.col_offset, name=name,
            owner=owner.qname if owner else None, inner=inner,
            call=call, counter=_counter_of(inner),
            donate=_donate_spec(call), static_args=snames,
            static_positions=spos,
            in_loop=_in_loop(parents, node,
                             owner.node if owner else None)))

    # decorator-form jit first (so the Call in decorator_list is not
    # double-counted as a free-standing wrapper)
    for fi in _iter_functions(mod):
        for dec in fi.node.decorator_list:
            is_jit = dotted_name(dec) in JIT_NAMES
            call = None
            if isinstance(dec, ast.Call):
                if _partial_of_jit(dec) is not None \
                        or dotted_name(dec.func) in JIT_NAMES:
                    is_jit, call = True, dec
            if is_jit:
                if call is not None:
                    seen_calls.add(id(call))
                add("jit", dec if call else fi.node, fi.node, fi.name,
                    call, fi)
                break

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or id(node) in seen_calls:
            continue
        d = dotted_name(node.func)
        if d is None:
            continue
        leaf = d.split(".")[-1]
        owner = _owner_info(parents, node, node_to_fi)
        scope = _enclosing(parents, node, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)) \
            or None
        if d in JIT_NAMES or _partial_of_jit(node) is not None:
            target = None
            if _partial_of_jit(node) is not None:
                target = node.args[1] if len(node.args) > 1 else None
            elif node.args:
                target = node.args[0]
            inner, name = _resolve_jit_target(target, scope or mod.tree,
                                              mod)
            add("jit", node, inner, name, node, owner)
        elif leaf == "shard_map":
            target = node.args[0] if node.args else None
            inner, name = _resolve_jit_target(target, scope or mod.tree,
                                              mod)
            add("shard_map", node, inner, name, node, owner)
        elif leaf == "pallas_call":
            target = node.args[0] if node.args else None
            inner, name = _resolve_jit_target(target, scope or mod.tree,
                                              mod)
            add("pallas_call", node, inner, name, node, owner)
        elif leaf in _AOT_LEAFS:
            root_name = d.split(".")[0]
            target = mod.imports.get(root_name)
            if target is None and scope is not None and owner is not None:
                target = _fn_local_imports(mod, owner.node).get(
                    root_name)
            if target is not None and "export" in target:
                add("aot-export", node, None, leaf, node, owner)


def _attach_holders(project: Project, unit: CompileUnit,
                    assign_edges: Dict[str, List],
                    return_edges: Dict[str, List[str]],
                    by_qname: Dict[str, FunctionInfo]) -> None:
    """Where does the compiled callable LIVE?  Direct ``self.X = jit(f)``
    assignments, module-level names, and factory-return chains
    (``self._fn = self._build()``, transitively through builders)."""
    if unit.kind == "aot-export":
        unit.memoized = True
        return
    owner = by_qname.get(unit.owner) if unit.owner else None
    holders: Set[str] = set()
    memo = False
    returned = False
    local_name: Optional[str] = None

    # decorator-form jit: the def IS the program, built once at import;
    # its own name is the holder call sites resolve against
    if owner is not None and unit.inner is owner.node:
        unit.holders = (owner.name,)
        unit.memoized = True
        return

    scope = owner.node if owner is not None else None
    if scope is not None and unit.call is not None:
        for n in ast.walk(scope):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and n.value is unit.call:
                t = n.targets[0]
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id in ("self", "cls"):
                    holders.add(t.attr)
                    if _has_none_guard(scope, t.attr):
                        memo = True
                elif isinstance(t, ast.Name):
                    local_name = t.id
            elif isinstance(n, ast.Return) and (
                    n.value is unit.call
                    or _binds_operands_of(n.value, unit.call)):
                returned = True
        if local_name is not None:
            holders.add(local_name)
            for n in ast.walk(scope):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and isinstance(n.targets[0], ast.Subscript) \
                        and isinstance(n.value, ast.Name) \
                        and n.value.id == local_name:
                    memo = True            # module dict cache idiom
                elif isinstance(n, ast.Return) \
                        and isinstance(n.value, ast.Name) \
                        and n.value.id == local_name:
                    returned = True
        # a unit inside a nested def that the owner returns is returned
        inner_def = _nested_def_containing(scope, unit)
        if inner_def is not None:
            for n in ast.walk(scope):
                if isinstance(n, ast.Return) \
                        and isinstance(n.value, ast.Name) \
                        and n.value.id == inner_def.name:
                    returned = True
    elif unit.owner is None and unit.call is not None:
        memo = True                         # module level: built once
        # module-level `NAME = jax.jit(f)` — the name is the holder
        # (call sites resolve against it for key classification)
        mod = project.modules.get(unit.module)
        if mod is not None:
            for n in ast.walk(mod.tree):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and n.value is unit.call \
                        and isinstance(n.targets[0], ast.Name):
                    holders.add(n.targets[0].id)
    if unit.inner is not None and unit.owner is None:
        memo = True

    if returned and owner is not None:
        frontier = [owner.qname]
        for _ in range(_MAX_BUILDER_DEPTH):
            nxt: List[str] = []
            for q in frontier:
                for (fi, name, is_attr) in assign_edges.get(q, ()):
                    holders.add(name)
                    if is_attr and _has_none_guard(fi.node, name):
                        memo = True
                nxt.extend(return_edges.get(q, ()))
            if not nxt:
                break
            frontier = nxt
    unit.holders = tuple(sorted(holders))
    unit.memoized = memo or unit.owner is None


def _binds_operands_of(node: ast.AST, call: ast.AST) -> bool:
    """``functools.partial(<the jit call>, weights...)``: the compiled
    program returned with its leading operands bound (how the engine
    hands its programs their weights) is still that program."""
    return (isinstance(node, ast.Call)
            and dotted_name(node.func) in PARTIAL_NAMES
            and bool(node.args) and node.args[0] is call)


def _nested_def_containing(scope: ast.AST,
                           unit: CompileUnit) -> Optional[ast.AST]:
    target = unit.call if unit.call is not None else unit.inner
    if target is None:
        return None
    for n in ast.walk(scope):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n is not scope:
            for sub in ast.walk(n):
                if sub is target:
                    return n
    return None


def _has_none_guard(scope: ast.AST, attr: str) -> bool:
    for n in ast.walk(scope):
        if isinstance(n, ast.Compare) and len(n.ops) == 1 \
                and isinstance(n.ops[0], (ast.Is, ast.IsNot)):
            sides = [n.left] + list(n.comparators)
            has_attr = any(isinstance(s, ast.Attribute)
                           and s.attr == attr for s in sides)
            has_none = any(isinstance(s, ast.Constant)
                           and s.value is None for s in sides)
            if has_attr and has_none:
                return True
    return False


def _classify_unit(project: Project, unit: CompileUnit,
                   taint_for, by_qname: Dict[str, FunctionInfo]) -> None:
    legs: List[str] = []
    rank = 0                       # 0 static, 1 bucketed, 2 unbounded
    if unit.donate:
        legs.append("donate=" + ",".join(map(str, unit.donate)))
    if unit.kind == "shard_map":
        legs.append("mesh/tp: shard_map program (one per mesh config)")
    if unit.kind == "pallas_call":
        legs.append("pallas grid (static per shape config)")

    # graftshape pass over the traced body: a DYN extent inside the
    # traced body IS an unbounded key (each distinct runtime value
    # compiles — or fails to trace)
    if unit.kind == "jit" and unit.inner is not None:
        from .absint import interpret_function
        traced = set(param_names(unit.inner)) - set(unit.static_args)
        traced.discard("self")
        fi = by_qname.get(unit.owner) if unit.owner else None
        try:
            interp = interpret_function(
                unit.inner, traced=traced, module_name=unit.module,
                project=project, cls=fi.cls if fi else None)
            events = list(interp.events)
        except Exception:
            events = []
        if events:
            rank = 2
            unit.evidence = (f"{events[0].detail} at "
                             f"{unit.relpath}:{events[0].node.lineno}")
            legs.append("traced body: data-dependent shape (DYN)")

    # call sites: classify every argument fed to the held program
    mod = project.modules.get(unit.module)
    if mod is not None and (unit.holders or unit.name):
        bt = taint_for(mod)
        names = set(unit.holders)
        for fi in _iter_functions(mod):
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                called = None
                if isinstance(f, ast.Attribute) and f.attr in names:
                    called = f.attr
                elif isinstance(f, ast.Name) and f.id in names:
                    called = f.id
                if called is None:
                    continue
                for i, a in enumerate(node.args):
                    if isinstance(a, ast.Starred):
                        continue
                    if i in unit.static_positions \
                            and _data_dependent(a):
                        rank = max(rank, 2)
                        unit.evidence = (
                            f"static arg {i} fed a data-dependent "
                            f"Python value at {fi.relpath}:"
                            f"{node.lineno}")
                        legs.append(f"arg[{i}]: unbounded "
                                    f"(data-dependent static value)")
                    elif bt.tainted_expr(a, fi):
                        rank = max(rank, 1)
                        legs.append(f"arg[{i}]: bucketed "
                                    f"(bucket-producer dataflow)")
    unit.key_class = {0: "trace-static", 1: "bucketed",
                      2: "unbounded"}[rank]
    unit.key_legs = tuple(sorted(set(legs)))


def surface_for(project: Project) -> Surface:
    """The per-project surface cache — the checker and the manifest
    share one build per analysis run."""
    surf = getattr(project, "_graftprog_surface", None)
    if surf is None:
        surf = build_surface(project)
        setattr(project, "_graftprog_surface", surf)
    return surf


# ----------------------------------------------------------- manifest

def build_manifest(project: Project) -> Dict:
    """The deterministic JSON program manifest: every compile unit with
    its static key, grouped per entry point and per counter plane.  This
    is the AOT exporter's build-time input (ROADMAP direction 2): the
    list of programs to lower ahead of time, with the bound that makes
    the set finite."""
    surface = surface_for(project)
    class_roots: Dict[str, List[CompileUnit]] = {}
    for unit in surface.units:
        for root in unit.roots:
            if unit.counter is not None:
                group = surface.root_groups.get(root, root)
                class_roots.setdefault(group, []).append(unit)

    planes: Dict[str, Dict] = {}
    for cls_qname, units in class_roots.items():
        counters: Dict[str, List[CompileUnit]] = {}
        for u in units:
            counters.setdefault(u.counter, []).append(u)
        plane: Dict[str, Dict] = {}
        for counter, us in counters.items():
            us = sorted({u.uid: u for u in us}.values(),
                        key=lambda u: u.uid)
            holder_groups = sorted({u.holders or (u.uid,) for u in us})
            if any(u.key_class == "unbounded" for u in us):
                bound, space = "unbounded", "unbounded"
            elif any(u.key_class == "bucketed" for u in us):
                bound, space = "O(log2) shape buckets", "bucketed"
            else:
                # units sharing a holder are config-selected VARIANTS
                # of one program slot: at most one compiles per process
                bound, space = str(len(holder_groups)), "trace-static"
            plane[counter] = {
                "programs": [u.uid for u in us],
                "holders": sorted({h for u in us for h in u.holders}),
                "key_space": space,
                "upper_bound": bound,
            }
        planes[cls_qname] = plane

    per_root: Dict[str, List[str]] = {}
    for unit in surface.units:
        for root in unit.roots:
            per_root.setdefault(root, []).append(unit.uid)

    return {
        "graftprog_version": 1,
        "entry_points": {
            "roots": {q: how for q, how in sorted(surface.roots.items())},
            "table": sorted(registered_entry_points()),
        },
        "programs": [u.to_json() for u in surface.units],
        "per_entry_point": {r: sorted(set(ids))
                            for r, ids in sorted(per_root.items())},
        "planes": planes,
        "unreachable": sorted(u.uid for u in surface.units
                              if not u.roots),
    }


def build_manifest_for_paths(paths: Sequence[str],
                             root: Optional[str] = None,
                             cache_path: Optional[str] = None) -> Dict:
    """Parse ``paths`` (through the shared on-disk parse cache when
    given), build the project index, and return the manifest — the CLI's
    ``--manifest`` entry point and the runtime consistency test's
    library hook."""
    import os
    from pathlib import Path
    from .walker import _ParseCache, _parse_files
    root_str = str(Path(root).resolve()) if root else os.getcwd()
    cache = _ParseCache(cache_path)
    parsed = _parse_files(paths, root_str, cache)
    cache.save()
    project = build_project((pf.relpath, pf.tree, pf.sup)
                            for pf in parsed.values()
                            if pf.tree is not None)
    return build_manifest(project)
