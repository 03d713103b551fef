"""resource-lifecycle: acquired handles must be released on every path.

The serving stack is full of host-side resource accounting whose bugs no
numeric test sees: a ``KVPool`` slot allocated and then leaked when an
exception fires before the request is placed, a ``BlockPool`` row freed
twice, a ``PrefixCache`` pin never unpinned.  This rule tracks REGISTERED
alloc/free method pairs through each function's control flow:

  * **exception-edge leak** — a handle is acquired, at least one
    statement that can raise (any call) runs before its release/escape,
    and no enclosing ``try`` releases it in an ``except``/``finally``
    block: the handle leaks on the exception path;
  * **plain leak** — acquired, never released, never escapes;
  * **double-free** — released again when already (definitely) released
    on every path;
  * **pin/unpin imbalance** — the same machinery applied to refcount
    pairs (``pin``/``unpin``, ``match``/``release``): a pin that can
    exit the function unreleased and unescaped is an imbalance.

Ownership transfer ends tracking: returning/yielding the handle, storing
it into an attribute/subscript/container, or passing it to any call
other than its release hands responsibility to the receiver (the rule
checks the window where THIS function owns the handle).

Pair registration API — pass ``pairs=(ResourcePair(...), ...)`` to the
checker (or extend :data:`DEFAULT_PAIRS`): ``acquire``/``release`` are
method names matched at call sites; ``receiver_hint`` restricts matching
to receiver expressions containing one of the substrings (keeps
``re.match`` out of the ``PrefixCache.match``/``release`` pair).
``alt_release`` names ADDITIONAL closing methods for protocols with more
than one legal terminal — the fleet KV handoff's ``stage`` closes with
``commit`` OR ``abort``, and a replica ``drain`` window closes with
``undrain`` OR permanent ``retire``; any of them balances the acquire.
Two acquire shapes are understood: ``h = recv.alloc()`` (handle = the
bound name) and ``recv.pin(x)`` / ``lock.acquire()`` (handle = the
argument, or the receiver itself when there is none).  An acquire whose
result is consumed inline (``return pool.alloc()``, ``use(pool.alloc())``)
escapes immediately and is never tracked.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..findings import Finding, ERROR
from .base import Checker

__all__ = ["ResourcePair", "DEFAULT_PAIRS", "ResourceLifecycleChecker"]


@dataclass(frozen=True)
class ResourcePair:
    """One registered alloc/free (or pin/unpin) method-name pair.
    ``alt_release`` lists additional closing method names — protocols
    with several legal terminals (commit-or-abort, undrain-or-retire)
    register them here and any one balances the acquire."""
    acquire: str
    release: str
    kind: str                           # human label for messages
    receiver_hint: Tuple[str, ...] = ()  # require a substring, () = any
    alt_release: Tuple[str, ...] = ()    # extra closing method names

    @property
    def releases(self) -> Tuple[str, ...]:
        return (self.release,) + self.alt_release

    def receiver_ok(self, recv_text: str) -> bool:
        if not self.receiver_hint:
            return True
        return any(h in recv_text for h in self.receiver_hint)


DEFAULT_PAIRS: Tuple[ResourcePair, ...] = (
    # kv_pool.KVPool slots and kv_pool.BlockPool rows
    ResourcePair("alloc", "free", "pool slot/row"),
    # generic lock/resource protocol (threading locks, semaphores)
    ResourcePair("acquire", "release", "resource"),
    # refcount pins
    ResourcePair("pin", "unpin", "refcount pin"),
    # prefix_cache.PrefixCache.match pins the radix path until release
    ResourcePair("match", "release", "radix prefix pin",
                 receiver_hint=("cache",)),
    # serving/faults.py FaultInjector: an armed injection point must be
    # disarmed on every exit path, or a raising chaos scenario leaves
    # the fault live for whatever runs next (hinted to fault-ish
    # receivers so tracer.enable/disable below keeps its own pair; this
    # pair must sort BEFORE the tracer one — acquire-name collisions
    # resolve first-match by receiver hint)
    ResourcePair("enable", "disable", "fault injection",
                 receiver_hint=("fault",)),
    # serving/router.py Router: a drained replica takes no new work —
    # a drain leaked on an exception edge silently shrinks the fleet
    # until an operator notices, so every drain must undrain (return to
    # rotation) or retire (permanent, drained removal) on all paths
    # (rebuild success OR failure)
    ResourcePair("drain", "undrain", "replica drain",
                 receiver_hint=("router",), alt_release=("retire",)),
    # serving/handoff.py HandoffManager: a staged KV handoff pins the
    # prompt's radix path on the prefill replica — a stage that reaches
    # neither commit nor abort leaks the pin (those blocks can never be
    # evicted again), so the window must close on every path
    ResourcePair("stage", "commit", "kv handoff",
                 receiver_hint=("handoff",), alt_release=("abort",)),
    # serving/autoscaler.py Autoscaler: a spawned decode replica must
    # eventually retire (drain-based removal) or capacity accounting
    # silently drifts — the spawn/retire window is the autoscaled
    # replica's lifetime
    ResourcePair("spawn", "retire", "autoscaled replica",
                 receiver_hint=("scaler",)),
    # serving/router.py hedged requests (docs/serving.md "Tail
    # latency"): an issued hedge runs one request on TWO replicas —
    # the race must end in resolve_hedge (the hedge won, the primary
    # was purged) or purge_hedge (the hedge lost and unwinds) on every
    # path, or the loser's slot and radix pins leak on its replica
    ResourcePair("issue_hedge", "resolve_hedge", "hedged request",
                 receiver_hint=("router",),
                 alt_release=("purge_hedge",)),
    # serving/journal.py Journal: an open journal holds an OS file
    # handle and an unflushed tail — a journal leaked on an exception
    # path silently stops journaling AND pins the fd; close() is the
    # graceful terminal, crash() the simulated-SIGKILL one (chaos/test
    # helper).  Hinted to journal-ish receivers (both the factory
    # classmethod `Journal.open` and a bound `journal` variable) so
    # file/zipfile/module `open` call sites stay untracked
    ResourcePair("open", "close", "request journal",
                 receiver_hint=("journal", "Journal"),
                 alt_release=("crash",)),
    # serving/aot.py AOTStore: a reader handle opened on the program
    # store must close on every path; hinted like the journal so plain
    # file `open` call sites stay untracked
    ResourcePair("open", "close", "aot program store",
                 receiver_hint=("aot", "AOTStore", "store")),
    # serving/aot.py AOTStore.create: an in-flight store build must
    # terminate in publish (success) or discard (abort) on every path,
    # or crashed builds leak half-written objects with no gc intent
    ResourcePair("create", "publish", "aot store build",
                 receiver_hint=("AOTStore",),
                 alt_release=("discard",)),
    # serving/journal.py segment rotation: a begun segment must seal
    # (flush + fsync + close) before the next begins, or two active
    # tails interleave and the torn-tail recovery contract breaks
    ResourcePair("begin_segment", "seal_segment", "journal segment",
                 receiver_hint=("journal",)),
    # serving/health.py EngineHealth: a quarantine window opened by the
    # watchdog must close on every path (rebuild success OR failure), or
    # the engine reports quarantined forever
    ResourcePair("enter_quarantine", "leave_quarantine",
                 "quarantine window", receiver_hint=("health",)),
    # obs.Tracer spans (paddle_tpu/obs/tracing.py): a begun span must be
    # ended on exception edges too, or every later span nests inside a
    # phantom (the engine's serving.step pattern — end_span in finally)
    ResourcePair("begin_span", "end_span", "trace span",
                 receiver_hint=("tracer", "obs")),
    # serving/metrics.py ServingMetrics: a step's serving.step span and
    # its open step.<phase> child (trace annotations too) close in the
    # engine's finally — end_step closes the phase first, so they nest
    ResourcePair("begin_step", "end_step", "step span set",
                 receiver_hint=("metrics",)),
    # obs.Tracer capture sessions: an enable without a guaranteed
    # disable leaves a tracer recording (and its profiler source live)
    # after the workload raised
    ResourcePair("enable", "disable", "tracer capture",
                 receiver_hint=("tracer",)),
)

_ACQ, _REL = "acq", "rel"


@dataclass
class _Handle:
    pair: ResourcePair
    recv: str                 # receiver text at acquire
    text: str                 # handle expression text
    node: ast.AST             # acquire site
    states: Set[str] = field(default_factory=lambda: {_ACQ})
    raise_between: bool = False
    protected: bool = False   # an enclosing try releases it on failure


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def _method_call(node: ast.AST) -> Optional[Tuple[str, str, ast.Call]]:
    """(receiver_text, method_name, call) for ``recv.meth(...)``."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        return _unparse(node.func.value), node.func.attr, node
    return None


class ResourceLifecycleChecker(Checker):
    name = "resource-lifecycle"
    severity = ERROR

    def __init__(self, pairs: Sequence[ResourcePair] = DEFAULT_PAIRS):
        self.pairs = tuple(pairs)
        self._release_names = {name for p in self.pairs
                               for name in p.releases}

    def check(self, ctx) -> List[Finding]:
        findings: List[Finding] = []
        accounting = self._accounting_methods(ctx.tree)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if id(node) in accounting:
                    continue
                self._scan_fn(ctx, node, findings)
        return findings

    def _accounting_methods(self, tree) -> Set[int]:
        """ids of method defs that ARE a registered pair's implementation
        — a class defining BOTH ends of a pair (e.g. KVPool.alloc/free,
        PrefixCache.match/release) owns the accounting, and its own
        bodies are not clients of it.  A lone function that merely shares
        a name (``def match(...)`` in a router) is still analyzed."""
        out: Set[int] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            methods = {m.name: m for m in node.body
                       if isinstance(m, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            for pair in self.pairs:
                defined = [r for r in pair.releases if r in methods]
                if pair.acquire in methods and defined:
                    out.add(id(methods[pair.acquire]))
                    for r in defined:
                        out.add(id(methods[r]))
        return out

    # -------------------------------------------------------- function
    def _scan_fn(self, ctx, fn, findings: List[Finding]) -> None:
        handles: Dict[Tuple[str, str], _Handle] = {}
        self._scan_suite(ctx, fn.body, handles, frozenset(), findings)
        for h in handles.values():
            if _ACQ in h.states:
                findings.append(Finding(
                    self.name, ctx.relpath, h.node.lineno,
                    h.node.col_offset,
                    f"{h.pair.kind} `{h.text}` acquired via "
                    f"{h.recv}.{h.pair.acquire}() has no matching "
                    f"{'/'.join(h.pair.releases)}() and never escapes "
                    f"this function on some path — leaked handle",
                    self.severity))

    # ----------------------------------------------------------- suites
    def _scan_suite(self, ctx, stmts, handles, protected_sigs,
                    findings) -> None:
        for stmt in stmts:
            self._scan_stmt(ctx, stmt, handles, protected_sigs, findings)

    def _release_sigs(self, node: ast.AST) -> Set[Tuple[str, str, str]]:
        """(release_method, receiver, handle_text) triples for every
        registered release call under ``node`` — used to pre-scan except/
        finally suites for protection."""
        out: Set[Tuple[str, str, str]] = set()
        for sub in ast.walk(node):
            mc = _method_call(sub)
            if mc is None:
                continue
            recv, meth, call = mc
            if meth not in self._release_names:
                continue
            harg = _unparse(call.args[0]) if call.args else recv
            out.add((meth, recv, harg))
        return out

    def _scan_stmt(self, ctx, stmt, handles, protected_sigs,
                   findings) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return        # nested defs own their handles separately
        if isinstance(stmt, ast.If):
            b1 = {k: _copy_handle(h) for k, h in handles.items()}
            b2 = {k: _copy_handle(h) for k, h in handles.items()}
            self._scan_suite(ctx, stmt.body, b1, protected_sigs, findings)
            self._scan_suite(ctx, stmt.orelse, b2, protected_sigs,
                             findings)
            self._join(handles, b1, b2)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            pre = {k: _copy_handle(h) for k, h in handles.items()}
            body = {k: _copy_handle(h) for k, h in handles.items()}
            self._scan_suite(ctx, stmt.body, body, protected_sigs,
                             findings)
            self._scan_suite(ctx, stmt.orelse, body, protected_sigs,
                             findings)
            self._join(handles, body, pre)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                pseudo = ast.copy_location(
                    ast.Expr(value=item.context_expr), item.context_expr)
                self._simple_effects(ctx, pseudo, handles, protected_sigs,
                                     findings)
            self._scan_suite(ctx, stmt.body, handles, protected_sigs,
                             findings)
            return
        if isinstance(stmt, ast.Try):
            # releases in except/finally suites protect every handle that
            # is live (or acquired) inside the try from exception leaks,
            # and count as the release itself once the suites run
            sigs = set(protected_sigs)
            for h in stmt.handlers:
                sigs |= self._release_sigs(h)
            sigs |= self._release_sigs(ast.Module(body=stmt.finalbody,
                                                  type_ignores=[]))
            for h in handles.values():
                if self._sig_matches(h, sigs):
                    h.protected = True
            entry = {k: _copy_handle(h) for k, h in handles.items()}
            self._scan_suite(ctx, stmt.body, handles, sigs, findings)
            self._scan_suite(ctx, stmt.orelse, handles, protected_sigs,
                             findings)
            # each handler runs from (an approximation of) the state at
            # try ENTRY — the body may not have reached its own release
            # when the exception fired, so a handler's release is NOT a
            # double free of the body's
            for hdl in stmt.handlers:
                hstate = {k: _copy_handle(h) for k, h in entry.items()}
                self._scan_suite(ctx, hdl.body, hstate, protected_sigs,
                                 findings)
                self._join(handles, dict(handles), hstate)
            self._scan_suite(ctx, stmt.finalbody, handles, protected_sigs,
                             findings)
            return
        self._simple_effects(ctx, stmt, handles, protected_sigs, findings)

    def _join(self, handles, b1, b2) -> None:
        handles.clear()
        for k in set(b1) | set(b2):
            h1, h2 = b1.get(k), b2.get(k)
            if h1 is None:
                handles[k] = h2
            elif h2 is None:
                handles[k] = h1
            else:
                h1.states |= h2.states
                h1.raise_between |= h2.raise_between
                h1.protected |= h2.protected
                handles[k] = h1

    # ------------------------------------------------ simple statements
    def _simple_effects(self, ctx, stmt, handles, protected_sigs,
                        findings) -> None:
        """Releases -> raise-marking -> escapes -> new acquires, within
        one simple statement."""
        calls: List[Tuple[str, str, ast.Call]] = []
        has_raise = isinstance(stmt, (ast.Raise, ast.Assert))
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                has_raise = True
                mc = _method_call(sub)
                if mc is not None:
                    calls.append(mc)

        released_now: Set[Tuple[str, str]] = set()
        # 1. releases
        for recv, meth, call in calls:
            if meth not in self._release_names:
                continue
            harg = _unparse(call.args[0]) if call.args else recv
            for key, h in list(handles.items()):
                # two legal release shapes: the ACQUIRE receiver
                # releases the handle (`pool.free(slot)`), or the
                # HANDLE releases itself (`journal.close()` balancing
                # `journal = Journal.open(...)` — the factory-open
                # protocol, where the classmethod receiver never
                # reappears)
                if meth not in h.pair.releases or h.text != harg \
                        or (h.recv != recv and h.text != recv):
                    continue
                if h.states == {_REL}:
                    findings.append(Finding(
                        self.name, ctx.relpath, call.lineno,
                        call.col_offset,
                        f"double {meth} of {h.pair.kind} `{h.text}` — "
                        f"already released on every path since the "
                        f"{h.pair.acquire} at line {h.node.lineno}",
                        self.severity))
                    continue
                if h.raise_between and not h.protected:
                    findings.append(Finding(
                        self.name, ctx.relpath, h.node.lineno,
                        h.node.col_offset,
                        f"{h.pair.kind} `{h.text}` leaks if an exception "
                        f"fires between {h.recv}.{h.pair.acquire}() "
                        f"(line {h.node.lineno}) and its {meth} (line "
                        f"{call.lineno}); release it in a finally/except "
                        f"path", self.severity))
                h.states = {_REL}
                h.raise_between = False
                released_now.add(key)

        # 2. raise potential for still-acquired handles
        if has_raise:
            for key, h in handles.items():
                if key not in released_now and _ACQ in h.states:
                    h.raise_between = True

        # 3. escapes: the handle text read anywhere but its release call
        escaped: List[Tuple[str, str]] = []
        for key, h in handles.items():
            if key in released_now or _ACQ not in h.states:
                continue
            if self._escapes(stmt, h):
                if h.raise_between and not h.protected:
                    findings.append(Finding(
                        self.name, ctx.relpath, h.node.lineno,
                        h.node.col_offset,
                        f"{h.pair.kind} `{h.text}` leaks if an exception "
                        f"fires between {h.recv}.{h.pair.acquire}() "
                        f"(line {h.node.lineno}) and the hand-off at "
                        f"line {stmt.lineno}; release it in a finally/"
                        f"except path", self.severity))
                escaped.append(key)
        for key in escaped:
            del handles[key]

        # 4. rebinding the handle name forgets the old handle
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                ttext = _unparse(t)
                for key in [k for k, h in handles.items()
                            if h.text == ttext]:
                    del handles[key]

        # 5. new acquires: h = recv.alloc()  /  recv.pin(x)
        self._collect_acquires(stmt, handles, protected_sigs)

    def _collect_acquires(self, stmt, handles, protected_sigs) -> None:
        value = None
        target_text = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            value = stmt.value
            target_text = stmt.targets[0].id
        elif isinstance(stmt, ast.Expr):
            value = stmt.value
        if value is None:
            return
        mc = _method_call(value)
        if mc is None:
            return
        recv, meth, call = mc
        for pair in self.pairs:
            if meth != pair.acquire or not pair.receiver_ok(recv):
                continue
            if target_text is not None:
                text = target_text
            elif call.args:
                text = _unparse(call.args[0])
                if not isinstance(call.args[0], (ast.Name, ast.Attribute)):
                    return    # untrackable handle expression
            else:
                text = recv
            h = _Handle(pair=pair, recv=recv, text=text, node=call)
            if self._sig_matches(h, protected_sigs):
                h.protected = True
            handles[(recv + "." + pair.acquire, text)] = h
            return

    def _sig_matches(self, h: _Handle,
                     sigs: Set[Tuple[str, str, str]]) -> bool:
        # same two release shapes as the main loop: acquire-receiver
        # release, or the handle releasing itself (factory-open)
        return any(meth in h.pair.releases and harg == h.text
                   and (recv == h.recv or recv == h.text)
                   for meth, recv, harg in sigs)

    def _escapes(self, stmt, h: _Handle) -> bool:
        """Does this statement hand the handle off — return/yield it,
        store it into a structure, or pass it to a non-release call?"""
        text = h.text
        if isinstance(stmt, ast.Return) and stmt.value is not None \
                and self._contains_text(stmt.value, text):
            return True
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, (ast.Yield, ast.YieldFrom)) \
                    and sub.value is not None \
                    and self._contains_text(sub.value, text):
                return True
            if isinstance(sub, ast.Assign):
                stores_out = any(
                    not isinstance(t, ast.Name) for t in sub.targets)
                if stores_out and self._contains_text(sub.value, text):
                    return True
                # h2 = h aliases the handle away from our tracking
                if any(isinstance(t, ast.Name) for t in sub.targets) \
                        and _unparse(sub.value) == text:
                    return True
            if isinstance(sub, ast.Call):
                mc = _method_call(sub)
                is_release = (mc is not None
                              and mc[1] in h.pair.releases
                              and mc[0] in (h.recv, h.text))
                if is_release:
                    continue
                for a in list(sub.args) + [k.value for k in sub.keywords]:
                    if self._contains_text(a, text):
                        return True
        return False

    def _contains_text(self, node: ast.AST, text: str) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Name, ast.Attribute, ast.Subscript)) \
                    and _unparse(sub) == text:
                return True
        return False


def _copy_handle(h: _Handle) -> _Handle:
    return _Handle(pair=h.pair, recv=h.recv, text=h.text, node=h.node,
                   states=set(h.states), raise_between=h.raise_between,
                   protected=h.protected)
