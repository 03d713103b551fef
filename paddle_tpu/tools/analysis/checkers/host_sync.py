"""host-sync: device-to-host transfers inside hot paths, now transitive.

Hot paths (configurable; defaults below) are where a blocking transfer
stalls the accelerator pipeline: Pallas kernel modules, the trainer's
step builders, the pipeline-schedule scan bodies, the serving step loop,
and the entry/script harness drivers.  Within them the checker flags:

  * ``.item()`` / ``.tolist()`` — synchronous readback;
  * ``.block_until_ready()`` — an explicit barrier (benchmarks belong in
    bench harnesses, not library hot paths);
  * ``jax.device_get(...)``;
  * ``np.asarray/np.array/np.ascontiguousarray`` on a computed value —
    a host copy (fine at module import or in data loading, not here);
  * ``float()/int()/bool()`` wrapped directly around a ``jnp.``/``jax.``
    computation or an indexed array — the classic "print the loss every
    step" sync;
  * **interprocedural (v2)**: a call to any project function that
    TRANSITIVELY reaches one of the syncs above, up to ``max_depth``
    call-graph hops — the helper that ``.item()``s two frames below the
    jitted body fires at the hot call site, with the call chain and the
    sink location in the message.  Needs the project index
    (``FileContext.project``); degrades to inline-only without it.

Which functions count as hot: in ``kernels/`` every function; elsewhere
only jit-traced functions and bodies passed to ``lax.scan`` /
``fori_loop`` / ``while_loop`` / ``cond`` — module-level helpers and data
prep in the same file stay free to touch the host.
"""

from __future__ import annotations

import ast
import fnmatch
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..findings import Finding, ERROR
from .base import (Checker, dotted_name, jit_decorator_info,
                   jitted_local_defs, loop_body_names, walk_with_class)

DEFAULT_HOT_PATHS = (
    "paddle_tpu/kernels/*.py",
    "paddle_tpu/models/trainer.py",
    "paddle_tpu/distributed/pipelining.py",
    # serving step loop: the engine's contract is ONE readback per step,
    # host-side — its jitted prefill/decode bodies must never sync
    "paddle_tpu/serving/*.py",
    # perf-critical entrypoints: their jitted step/generate bodies must
    # stay sync-free too (harness-level readbacks around them are host
    # code and stay legal; intentional in-body syncs carry suppressions)
    "__graft_entry__.py",
    "scripts/*.py",
)
_ALL_FUNCTIONS_PATHS = ("paddle_tpu/kernels/*.py",)
DEFAULT_MAX_DEPTH = 4

_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_DEVICE_GET = {"jax.device_get", "device_get"}
_NP_COPY = {"asarray", "array", "ascontiguousarray"}
_CONCRETIZERS = {"float", "int", "bool"}


def _numpy_aliases(tree: ast.Module) -> Set[str]:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out


def _has_nonliteral_arg(call: ast.Call) -> bool:
    return any(not isinstance(a, ast.Constant) for a in call.args)


def _is_device_expr(node: ast.AST) -> bool:
    """Does the expression textually involve a jnp./jax. computation —
    i.e. is the float() almost certainly wrapping a device value rather
    than a Python scalar?  (Bare names and host-side subscripts like a
    flags dict stay out of scope — the tracer-leak rule owns taint.)"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = dotted_name(sub.func)
            if d is not None and d.split(".")[0] in ("jnp", "jax"):
                return True
    return False


def direct_syncs(fn: ast.AST,
                 np_aliases: Set[str]) -> List[Tuple[ast.AST, str, str]]:
    """(node, message, short sink label) for every syntactically-inline
    host sync in ``fn`` — the shared sink definition for both the inline
    hot-path scan and the project-wide taint pass."""
    out: List[Tuple[ast.AST, str, str]] = []
    for sub in ast.walk(fn):
        if not isinstance(sub, ast.Call):
            continue
        fname = dotted_name(sub.func)
        if isinstance(sub.func, ast.Attribute) \
                and sub.func.attr in _SYNC_METHODS:
            # ".item" etc. on a module (np.asarray handled below), not
            # on np itself — receivers that are plain numpy aliases
            # are host-side already
            recv = dotted_name(sub.func.value)
            if recv not in np_aliases:
                out.append((sub, f".{sub.func.attr}() is a blocking "
                                 f"device->host sync in a hot path",
                            f".{sub.func.attr}()"))
            continue
        if fname in _DEVICE_GET:
            out.append((sub, "jax.device_get in a hot path is a blocking "
                             "device->host transfer", "jax.device_get"))
            continue
        if fname is not None and "." in fname:
            root, leaf = fname.split(".", 1)
            if root in np_aliases and leaf in _NP_COPY \
                    and _has_nonliteral_arg(sub):
                out.append((sub, f"{fname}() copies a computed value to "
                                 f"host in a hot path; use jnp.{leaf} to "
                                 f"stay on device", f"{fname}()"))
                continue
        if fname in _CONCRETIZERS and sub.args \
                and _is_device_expr(sub.args[0]):
            out.append((sub, f"{fname}() around a device computation "
                             f"forces a host sync in a hot path",
                        f"{fname}()"))
    return out




class _SyncTaint:
    """Project-wide 'reaches a host sync' map: reverse-BFS from every
    function with an inline sync, bounded at ``max_depth`` hops.  Entry:
    qname -> (next hop qname or None, sink label, sink relpath, sink
    line, depth)."""

    def __init__(self, project, max_depth: int):
        self.project = project
        self.max_depth = max_depth
        self.taint: Dict[str, Tuple[Optional[str], str, str, int, int]] = {}
        self._np_by_mod: Dict[str, Set[str]] = {}
        self._build()

    def _np_aliases(self, mod_name: str) -> Set[str]:
        hit = self._np_by_mod.get(mod_name)
        if hit is None:
            m = self.project.modules.get(mod_name)
            hit = _numpy_aliases(m.tree) if m is not None else set()
            self._np_by_mod[mod_name] = hit
        return hit

    def _suppressed(self, fi, node) -> bool:
        """A sink carrying its own reasoned ``disable=host-sync`` is an
        ACKNOWLEDGED sync — it must not taint every hot caller with
        findings that cannot be suppressed at the source."""
        m = self.project.modules.get(fi.module)
        sup = getattr(m, "sup", None) if m is not None else None
        if sup is None:
            return False
        from ..findings import Finding as _F
        return sup.matches(_F("host-sync", fi.relpath, node.lineno, 0, ""))

    def _build(self) -> None:
        fns = {fi.qname: fi for fi in self.project.all_functions()}
        rev: Dict[str, List[str]] = {}
        for fi in fns.values():
            for callee in self.project.callees(fi):
                rev.setdefault(callee.qname, []).append(fi.qname)
        frontier: List[str] = []
        for fi in fns.values():
            sinks = [(n, m, s)
                     for n, m, s in direct_syncs(fi.node,
                                                 self._np_aliases(fi.module))
                     if not self._suppressed(fi, n)]
            if sinks:
                node, _, short = sinks[0]
                self.taint[fi.qname] = (None, short, fi.relpath,
                                        node.lineno, 0)
                frontier.append(fi.qname)
        for depth in range(1, self.max_depth + 1):
            nxt: List[str] = []
            for q in frontier:
                for caller in rev.get(q, ()):
                    if caller in self.taint:
                        continue
                    _, short, rel, line, _ = self.taint[q]
                    self.taint[caller] = (q, short, rel, line, depth)
                    nxt.append(caller)
            frontier = nxt

    def chain(self, qname: str) -> List[str]:
        out: List[str] = []
        cur: Optional[str] = qname
        while cur is not None and cur in self.taint:
            out.append(cur)
            cur = self.taint[cur][0]
        return out


class HostSyncChecker(Checker):
    name = "host-sync"
    severity = ERROR

    def __init__(self, hot_paths: Optional[Sequence[str]] = None,
                 all_functions_paths: Optional[Sequence[str]] = None,
                 max_depth: int = DEFAULT_MAX_DEPTH):
        self.hot_paths = tuple(hot_paths or DEFAULT_HOT_PATHS)
        self.all_fn_paths = tuple(
            all_functions_paths
            if all_functions_paths is not None else _ALL_FUNCTIONS_PATHS)
        self.max_depth = max_depth
        self._taint_for = None       # (project, _SyncTaint) identity pair

    def check(self, ctx) -> List[Finding]:
        if not any(fnmatch.fnmatch(ctx.relpath, pat) for pat in self.hot_paths):
            return []
        everything_hot = any(fnmatch.fnmatch(ctx.relpath, pat)
                             for pat in self.all_fn_paths)
        np_aliases = _numpy_aliases(ctx.tree)
        wrapped = jitted_local_defs(ctx.tree)
        loop_bodies = loop_body_names(ctx.tree)
        taint = self._project_taint(ctx)

        findings: List[Finding] = []
        for node, cls in walk_with_class(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            hot = (everything_hot
                   or jit_decorator_info(node) is not None
                   or node.name in wrapped
                   or node.name in loop_bodies)
            if not hot:
                continue
            for sub, msg, _ in direct_syncs(node, np_aliases):
                findings.append(Finding(
                    self.name, ctx.relpath, sub.lineno, sub.col_offset,
                    msg, self.severity))
            if taint is not None:
                self._scan_transitive(ctx, node, cls, taint, findings)
        # in all-functions files an outer def's walk also covers its
        # nested defs, which are hot in their own right — dedupe
        seen: set = set()
        unique: List[Finding] = []
        for f in findings:
            key = (f.line, f.col, f.message)
            if key not in seen:
                seen.add(key)
                unique.append(f)
        return unique

    # ------------------------------------------------- interprocedural
    def _project_taint(self, ctx) -> Optional[_SyncTaint]:
        if ctx.project is None or self.max_depth < 1:
            return None
        if self._taint_for is None or self._taint_for[0] is not ctx.project:
            self._taint_for = (ctx.project,
                               _SyncTaint(ctx.project, self.max_depth))
        return self._taint_for[1]

    def _scan_transitive(self, ctx, fn, cls, taint: _SyncTaint,
                         findings: List[Finding]) -> None:
        mi = ctx.project.module_for(ctx.relpath)
        if mi is None:
            return
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            dotted = dotted_name(sub.func)
            target = ctx.project.resolve_call(mi.name, dotted, cls=cls)
            if target is None or target.node is fn:
                continue
            hit = taint.taint.get(target.qname)
            if hit is None:
                continue
            _, short, sink_rel, sink_line, _ = hit
            chain = taint.chain(target.qname)
            via = ""
            if len(chain) > 1:
                via = ", via " + " -> ".join(
                    q.rsplit(".", 1)[-1] + "()" for q in chain)
            findings.append(Finding(
                self.name, ctx.relpath, sub.lineno, sub.col_offset,
                f"{dotted}() reaches a blocking host sync in a hot path "
                f"({short} at {sink_rel}:{sink_line}{via})",
                self.severity))
