"""Project-wide symbol index + call graph for graftlint (import-free).

Per-file AST analysis goes blind exactly where the serving stack hurts:
a helper that syncs two frames below a jitted body, a ``donate_argnums``
spec declared in one method and violated in another, an axis name
declared by the module that *exports* the mesh.  ``Project`` gives
checkers a whole-program view without ever importing the code under
analysis — it is built purely from the parsed trees the walker already
holds:

  * **module resolution** — every scanned file gets a dotted module name
    relative to the scan root (``paddle_tpu/serving/engine.py`` ->
    ``paddle_tpu.serving.engine``; ``__graft_entry__.py`` ->
    ``__graft_entry__``), and both
    absolute and relative imports resolve to those names;
  * **symbol tables** — top-level functions, classes and their methods,
    plus module-level ``g = f`` aliases;
  * **call edges** — ``Project.callees(fn)`` resolves the dotted call
    sites of a function body (bare names, ``self.method``, imported
    names, ``module.attr`` chains) to ``FunctionInfo`` records, with
    alias tracking through imports and module-level rebinding.

Checkers receive the project on ``FileContext.project`` (``None`` when
the walker runs without one, e.g. ad-hoc single-file library calls — a
project-aware rule must degrade to its intraprocedural behaviour).

Resolution is deliberately best-effort and sound-for-linting: a call the
index cannot resolve (dynamic dispatch, ``getattr``, calls through
parameters) simply produces no edge — rules built on the graph can miss,
but what they DO resolve is real.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .checkers.base import dotted_name

__all__ = ["Project", "ModuleInfo", "ClassInfo", "FunctionInfo",
           "build_project", "module_name_for"]


@dataclass
class FunctionInfo:
    """One function or method definition."""
    qname: str                    # "pkg.mod.func" / "pkg.mod.Cls.method"
    module: str                   # dotted module name
    relpath: str                  # file the def lives in
    name: str
    node: ast.AST                 # the FunctionDef / AsyncFunctionDef
    cls: Optional[str] = None     # owning class name, if a method


@dataclass
class ClassInfo:
    name: str
    module: str
    node: ast.ClassDef
    bases: Tuple[str, ...] = ()   # dotted base-class names, textual
    methods: Dict[str, FunctionInfo] = field(default_factory=dict)


@dataclass
class ModuleInfo:
    name: str                     # dotted module name
    relpath: str
    tree: ast.Module
    is_pkg: bool = False          # file is an __init__.py
    sup: Optional[object] = None  # suppress.Suppressions, when provided
    # local alias -> fully-qualified dotted target ("np" -> "numpy",
    # "KVPool" -> "paddle_tpu.serving.kv_pool.KVPool")
    imports: Dict[str, str] = field(default_factory=dict)
    functions: Dict[str, FunctionInfo] = field(default_factory=dict)
    classes: Dict[str, ClassInfo] = field(default_factory=dict)
    aliases: Dict[str, str] = field(default_factory=dict)  # g = f rebinds
    # module-level NAME = "literal" string constants (AXIS = "tp") —
    # axis-name/sharding rules resolve non-literal axis args through them
    consts: Dict[str, str] = field(default_factory=dict)


def module_name_for(relpath: str) -> Tuple[str, bool]:
    """(dotted module name, is_package) for a root-relative posix path."""
    parts = relpath[:-3].split("/") if relpath.endswith(".py") \
        else relpath.split("/")
    if parts and parts[-1] == "__init__":
        return ".".join(parts[:-1]) or parts[0], True
    return ".".join(parts), False


def _package_parts(mod: ModuleInfo) -> List[str]:
    parts = mod.name.split(".")
    return parts if mod.is_pkg else parts[:-1]


class Project:
    """The whole-program index.  Build via :func:`build_project`."""

    def __init__(self):
        self.modules: Dict[str, ModuleInfo] = {}
        self.by_relpath: Dict[str, ModuleInfo] = {}
        self._callee_cache: Dict[str, Tuple[FunctionInfo, ...]] = {}
        self._attr_type_cache: Dict[Tuple[str, str], Dict] = {}

    # ------------------------------------------------------------ lookup
    def module_for(self, relpath: str) -> Optional[ModuleInfo]:
        return self.by_relpath.get(relpath)

    def all_functions(self) -> Iterable[FunctionInfo]:
        for m in self.modules.values():
            yield from m.functions.values()
            for c in m.classes.values():
                yield from c.methods.values()

    def imported_modules(self, mod_name: str) -> Set[str]:
        """Project modules this module imports (directly), resolved
        through both ``import x`` and ``from x import y`` forms."""
        m = self.modules.get(mod_name)
        if m is None:
            return set()
        out: Set[str] = set()
        for target in m.imports.values():
            hit = self._longest_module_prefix(target)
            if hit is not None and hit != mod_name:
                out.add(hit)
        return out

    def _longest_module_prefix(self, dotted: str) -> Optional[str]:
        parts = dotted.split(".")
        for i in range(len(parts), 0, -1):
            cand = ".".join(parts[:i])
            if cand in self.modules:
                return cand
        return None

    # -------------------------------------------------------- resolution
    def resolve_call(self, mod_name: str, dotted: Optional[str],
                     cls: Optional[str] = None) -> Optional[FunctionInfo]:
        """Resolve a textual call target seen in ``mod_name`` (optionally
        inside method context of class ``cls``) to a project function."""
        if not dotted:
            return None
        m = self.modules.get(mod_name)
        if m is None:
            return None
        parts = dotted.split(".")
        if parts[0] in ("self", "cls") and cls is not None \
                and len(parts) == 2:
            return self._method(mod_name, cls, parts[1])
        if len(parts) == 1:
            return self._local_function(m, parts[0], set())
        target = m.imports.get(parts[0])
        if target is not None:
            return self._global(".".join([target] + parts[1:]))
        # a fully-qualified name used verbatim (rare, but cheap to honour)
        return self._global(dotted)

    def _local_function(self, m: ModuleInfo, name: str,
                        seen: Set[str]) -> Optional[FunctionInfo]:
        if name in seen:
            return None
        seen.add(name)
        fi = m.functions.get(name)
        if fi is not None:
            return fi
        alias = m.aliases.get(name)
        if alias is not None:
            return self._local_function(m, alias, seen)
        target = m.imports.get(name)
        if target is not None:
            return self._global(target)
        return None

    def _global(self, dotted: str) -> Optional[FunctionInfo]:
        mod = self._longest_module_prefix(dotted)
        if mod is None or mod == dotted:
            return None
        m = self.modules[mod]
        rest = dotted[len(mod) + 1:].split(".")
        if len(rest) == 1:
            return self._local_function(m, rest[0], set())
        if len(rest) == 2:
            ci = m.classes.get(rest[0])
            if ci is not None:
                return ci.methods.get(rest[1])
        return None

    def _method(self, mod_name: str, cls: str, name: str,
                depth: int = 0) -> Optional[FunctionInfo]:
        m = self.modules.get(mod_name)
        if m is None or depth > 4:
            return None
        ci = m.classes.get(cls)
        if ci is None:
            # the class may live in another module (imported base context)
            fi = self._global(f"{mod_name}.{cls}.{name}")
            return fi
        fi = ci.methods.get(name)
        if fi is not None:
            return fi
        for base in ci.bases:
            bparts = base.split(".")
            if len(bparts) == 1:
                if bparts[0] in m.classes:
                    hit = self._method(mod_name, bparts[0], name, depth + 1)
                    if hit is not None:
                        return hit
                target = m.imports.get(bparts[0])
                if target is not None:
                    hit = self._global(f"{target}.{name}")
                    if hit is not None:
                        return hit
            else:
                target = m.imports.get(bparts[0])
                if target is not None:
                    hit = self._global(
                        ".".join([target] + bparts[1:] + [name]))
                    if hit is not None:
                        return hit
        return None

    def resolve_qname(self, dotted: str) -> Optional[FunctionInfo]:
        """Resolve a fully-qualified dotted name (``pkg.mod.fn`` /
        ``pkg.mod.Cls.method``) to a project function — the public form
        of the global lookup, used by graftprog's entry-point table."""
        return self._global(dotted)

    def resolve_class(self, mod_name: str,
                      dotted: Optional[str]) -> Optional[ClassInfo]:
        """Resolve a textual class reference seen in ``mod_name`` (bare
        local name, imported name, or ``module.Cls`` chain) to a project
        :class:`ClassInfo`."""
        if not dotted:
            return None
        m = self.modules.get(mod_name)
        if m is None:
            return None
        parts = dotted.split(".")
        if len(parts) == 1:
            ci = m.classes.get(parts[0])
            if ci is not None:
                return ci
            target = m.imports.get(parts[0])
            if target is not None:
                return self._global_class(target)
            return None
        target = m.imports.get(parts[0])
        if target is not None:
            return self._global_class(".".join([target] + parts[1:]))
        return self._global_class(dotted)

    def _global_class(self, dotted: str) -> Optional[ClassInfo]:
        mod = self._longest_module_prefix(dotted)
        if mod is None or mod == dotted:
            return None
        rest = dotted[len(mod) + 1:].split(".")
        if len(rest) == 1:
            return self.modules[mod].classes.get(rest[0])
        return None

    @staticmethod
    def _annotation_class_name(ann: Optional[ast.AST]) -> Optional[str]:
        """The class name a parameter/attribute annotation points at,
        unwrapping one ``Optional[...]``/single-arg subscript layer and
        PEP-563 string annotations."""
        if ann is None:
            return None
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            try:
                ann = ast.parse(ann.value, mode="eval").body
            except SyntaxError:
                return None
        if isinstance(ann, ast.Subscript):
            ann = ann.slice
        return dotted_name(ann)

    def class_attr_types(self, mod_name: str,
                         cls_name: str) -> Dict[str, Tuple[ClassInfo, ...]]:
        """``{attr: candidate ClassInfos}`` for ``self.<attr>`` of one
        class: inferred from ``self.x = Cls(...)`` / ``self.x =
        Cls.create(...)`` constructor assignments, ``self.x = param``
        where the param is class-annotated, and ``self.x: Cls`` /
        ``self.x: Optional[Cls]`` annotated assignments across every
        method.  Conflicting assignments keep ALL candidates — callers
        doing reachability must follow each (sound over-approximation)."""
        key = (mod_name, cls_name)
        hit = self._attr_type_cache.get(key)
        if hit is not None:
            return hit
        out: Dict[str, Dict[str, ClassInfo]] = {}
        m = self.modules.get(mod_name)
        ci = m.classes.get(cls_name) if m is not None else None

        def record(attr: str, target: Optional[ClassInfo]) -> None:
            if target is not None:
                out.setdefault(attr, {})[target.module + "." +
                                         target.name] = target

        for fi in (ci.methods.values() if ci is not None else ()):
            ann_types: Dict[str, Optional[str]] = {}
            a = fi.node.args
            for p in a.posonlyargs + a.args + a.kwonlyargs:
                ann_types[p.arg] = self._annotation_class_name(p.annotation)
            for node in ast.walk(fi.node):
                target = None
                value = None
                if isinstance(node, ast.Assign) and len(node.targets) == 1:
                    target, value = node.targets[0], node.value
                elif isinstance(node, ast.AnnAssign):
                    target = node.target
                    if not isinstance(target, ast.Attribute) \
                            or not isinstance(target.value, ast.Name) \
                            or target.value.id != "self":
                        continue
                    record(target.attr, self.resolve_class(
                        mod_name, self._annotation_class_name(
                            node.annotation)))
                    value = node.value
                if not isinstance(target, ast.Attribute) \
                        or not isinstance(target.value, ast.Name) \
                        or target.value.id != "self" or value is None:
                    continue
                if isinstance(value, ast.Call):
                    d = dotted_name(value.func)
                    if d is None:
                        continue
                    hit_cls = self.resolve_class(mod_name, d)
                    if hit_cls is None and "." in d:
                        # Cls.create(...) and friends: the class part
                        hit_cls = self.resolve_class(
                            mod_name, d.rsplit(".", 1)[0])
                    record(target.attr, hit_cls)
                elif isinstance(value, ast.Name) \
                        and ann_types.get(value.id):
                    record(target.attr, self.resolve_class(
                        mod_name, ann_types[value.id]))
        result = {attr: tuple(cands.values()) for attr, cands in out.items()}
        self._attr_type_cache[key] = result
        return result

    def resolve_str_const(self, mod_name: str,
                          dotted: Optional[str]) -> Optional[str]:
        """Resolve a textual reference seen in ``mod_name`` to a
        module-level string constant: bare names through local consts /
        ``g = f`` aliases / ``from m import C`` targets, dotted names
        (``topo.AXIS``) through the import table."""
        if not dotted:
            return None
        m = self.modules.get(mod_name)
        if m is None:
            return None
        parts = dotted.split(".")
        if len(parts) == 1:
            name = parts[0]
            seen: Set[str] = set()
            while name not in seen:
                seen.add(name)
                if name in m.consts:
                    return m.consts[name]
                if name in m.aliases:
                    name = m.aliases[name]
                    continue
                target = m.imports.get(name)
                if target is not None and "." in target:
                    owner, leaf = target.rsplit(".", 1)
                    om = self.modules.get(owner)
                    if om is not None and leaf in om.consts:
                        return om.consts[leaf]
                return None
            return None
        target = m.imports.get(parts[0])
        if target is not None and len(parts) == 2:
            om = self.modules.get(target)
            if om is not None:
                return om.consts.get(parts[1])
        return None

    # -------------------------------------------------------- call graph
    def callees(self, fn: FunctionInfo) -> Tuple[FunctionInfo, ...]:
        """Resolved project functions called (textually) inside ``fn``,
        nested defs included — defining a callable that syncs is treated
        like reaching it, a sound over-approximation for taint rules."""
        cached = self._callee_cache.get(fn.qname)
        if cached is not None:
            return cached
        out: List[FunctionInfo] = []
        seen: Set[str] = set()
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            target = self.resolve_call(fn.module, dotted_name(node.func),
                                       cls=fn.cls)
            if target is not None and target.qname != fn.qname \
                    and target.qname not in seen:
                seen.add(target.qname)
                out.append(target)
        result = tuple(out)
        self._callee_cache[fn.qname] = result
        return result


# --------------------------------------------------------------- builder

def _resolve_relative(mod: ModuleInfo, level: int,
                      module: Optional[str]) -> Optional[str]:
    pkg = _package_parts(mod)
    if level - 1 > len(pkg):
        return None
    base = pkg[:len(pkg) - (level - 1)]
    parts = base + (module.split(".") if module else [])
    return ".".join(parts) if parts else None


def _index_module(mod: ModuleInfo) -> None:
    for node in mod.tree.body:
        if isinstance(node, ast.Import):
            # ``import a.b as c`` binds the full path to ``c``; plain
            # ``import a.b`` binds only the root name ``a`` — but the
            # submodule is still imported, so record the full dotted
            # path under itself (never a bare name in code, and it lets
            # imported_modules() see ``a.b``)
            for a in node.names:
                if a.asname:
                    mod.imports[a.asname] = a.name
                else:
                    root = a.name.split(".")[0]
                    mod.imports[root] = root
                    if "." in a.name:
                        mod.imports[a.name] = a.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module if node.level == 0 else \
                _resolve_relative(mod, node.level, node.module)
            if base is None:
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                mod.imports[a.asname or a.name] = f"{base}.{a.name}"
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            mod.functions[node.name] = FunctionInfo(
                qname=f"{mod.name}.{node.name}", module=mod.name,
                relpath=mod.relpath, name=node.name, node=node)
        elif isinstance(node, ast.ClassDef):
            ci = ClassInfo(name=node.name, module=mod.name, node=node,
                           bases=tuple(b for b in
                                       (dotted_name(x) for x in node.bases)
                                       if b))
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    ci.methods[sub.name] = FunctionInfo(
                        qname=f"{mod.name}.{node.name}.{sub.name}",
                        module=mod.name, relpath=mod.relpath,
                        name=sub.name, node=sub, cls=node.name)
            mod.classes[node.name] = ci
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            if isinstance(node.value, ast.Name):
                mod.aliases[node.targets[0].id] = node.value.id
            elif isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str):
                mod.consts[node.targets[0].id] = node.value.value


def build_project(entries: Iterable[Tuple]) -> Project:
    """``entries`` yields (root-relative posix path, tree) or
    (relpath, tree, suppressions) — the suppressions let project-wide
    taint passes honour in-source directives at the sink."""
    project = Project()
    for entry in entries:
        relpath, tree = entry[0], entry[1]
        sup = entry[2] if len(entry) > 2 else None
        name, is_pkg = module_name_for(relpath)
        mod = ModuleInfo(name=name, relpath=relpath, tree=tree,
                         is_pkg=is_pkg, sup=sup)
        _index_module(mod)
        # first writer wins on name collisions (scan roots should not
        # overlap, but a duplicate must not silently shadow)
        project.modules.setdefault(name, mod)
        project.by_relpath[relpath] = mod
    return project
