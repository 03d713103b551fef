"""graftlint self-check: per-rule fixture tests + the repo-wide CI gate.

Fixtures live under tests/fixtures/lint/ — one positive (must fire) and
one negative (must stay silent) file per rule, plus suppression-syntax
files, two miniature registry trees, and two-module packages for the
cross-module axis-name resolution.  The gate test at the bottom is the
contract ISSUE 1 pins (and ISSUE 4 widens): zero unsuppressed findings
over the default scan scope — ``paddle_tpu/`` plus the perf-critical
entrypoints (``__graft_entry__.py``, ``scripts/``).
"""

import ast
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from paddle_tpu.tools.analysis import (Finding, default_checkers,
                                       parse_suppressions, run_analysis)
from paddle_tpu.tools.analysis.checkers.host_sync import HostSyncChecker
from paddle_tpu.tools.analysis.checkers.registry_drift import \
    RegistryDriftChecker

REPO_ROOT = Path(__file__).resolve().parents[1]
LINT = REPO_ROOT / "tests" / "fixtures" / "lint"
# keep in sync with scripts/graftlint.py DEFAULT_SCOPE
GATE_SCOPE = [str(REPO_ROOT / p)
              for p in ("paddle_tpu", "__graft_entry__.py", "scripts")]


def run_rule(filename, rule):
    return run_analysis([str(LINT / filename)], root=str(LINT), rules=[rule])


def only_rule(result, rule):
    return [f for f in result.findings if f.rule == rule]


# --------------------------------------------------------------- rule set

def test_rule_catalogue_is_complete():
    names = {c.name for c in default_checkers()}
    assert names == {"tracer-leak", "recompile-hazard", "host-sync",
                     "axis-name", "registry-drift", "dead-state",
                     "use-after-donate", "resource-lifecycle",
                     "recompile-shape", "dtype-flow",
                     "sharding-consistency", "compile-surface",
                     "memory-budget", "collective-order"}
    # ISSUE 20: the catalogue is now fourteen rules — a checker silently
    # dropping out of default_checkers() must fail loudly
    assert len(names) == 14 and len(default_checkers()) == 14


# ------------------------------------------------- per-rule fixture pairs

def test_tracer_leak_positive():
    res = run_rule("tracer_leak_pos.py", "tracer-leak")
    found = only_rule(res, "tracer-leak")
    assert len(found) == 4, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "float()" in msgs
    assert "`if`" in msgs
    assert "np.asarray" in msgs
    assert ".item()" in msgs


def test_tracer_leak_negative():
    res = run_rule("tracer_leak_neg.py", "tracer-leak")
    assert res.findings == [], [f.format() for f in res.findings]


def test_recompile_positive():
    res = run_rule("recompile_pos.py", "recompile-hazard")
    found = only_rule(res, "recompile-hazard")
    assert len(found) == 4, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "inside a loop" in msgs
    assert "lambda" in msgs
    assert "static arg" in msgs
    assert "@to_static" in msgs


def test_recompile_negative():
    res = run_rule("recompile_neg.py", "recompile-hazard")
    assert res.findings == [], [f.format() for f in res.findings]


def _host_sync_checker():
    # the rule keys on hot-path globs; point it at the fixtures and keep
    # "every function is hot" off so the negative file's helpers stay cold
    return HostSyncChecker(hot_paths=("host_sync_pos.py",
                                      "host_sync_neg.py"),
                           all_functions_paths=())


def test_host_sync_positive():
    res = run_analysis([str(LINT / "host_sync_pos.py")],
                       checkers=[_host_sync_checker()], root=str(LINT))
    found = only_rule(res, "host-sync")
    assert len(found) == 4, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert ".item()" in msgs
    assert "device_get" in msgs
    assert "copies a computed value" in msgs
    assert "float()" in msgs


def test_host_sync_negative():
    res = run_analysis([str(LINT / "host_sync_neg.py")],
                       checkers=[_host_sync_checker()], root=str(LINT))
    assert res.findings == [], [f.format() for f in res.findings]


def _serving_host_sync_checker():
    return HostSyncChecker(hot_paths=("serving_host_sync_pos.py",
                                      "serving_host_sync_neg.py"),
                           all_functions_paths=())


def test_serving_host_sync_positive():
    """Serving hot-loop idiom: per-step host syncs inside the compiled
    decode/scheduler bodies (the engine's one-readback-per-step contract
    violated four ways)."""
    res = run_analysis([str(LINT / "serving_host_sync_pos.py")],
                       checkers=[_serving_host_sync_checker()],
                       root=str(LINT))
    found = only_rule(res, "host-sync")
    assert len(found) == 4, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert ".item()" in msgs
    assert "float()" in msgs
    assert "device_get" in msgs
    assert "copies a computed value" in msgs


def test_serving_host_sync_negative():
    """The engine's legal shape: one host readback AFTER the dispatch,
    admission bookkeeping in plain host code — silent."""
    res = run_analysis([str(LINT / "serving_host_sync_neg.py")],
                       checkers=[_serving_host_sync_checker()],
                       root=str(LINT))
    assert res.findings == [], [f.format() for f in res.findings]


def test_serving_package_is_a_default_hot_path():
    """The shipped rule config must keep covering the serving step loop
    AND the perf-critical entrypoints ISSUE 4 widened the gate to."""
    import fnmatch
    from paddle_tpu.tools.analysis.checkers.host_sync import \
        DEFAULT_HOT_PATHS
    assert "paddle_tpu/serving/*.py" in DEFAULT_HOT_PATHS
    assert any(fnmatch.fnmatch("paddle_tpu/serving/prefix_cache.py", p)
               for p in DEFAULT_HOT_PATHS)
    assert "__graft_entry__.py" in DEFAULT_HOT_PATHS


def _prefix_host_sync_checker():
    return HostSyncChecker(hot_paths=("serving_prefix_host_sync_pos.py",
                                      "serving_prefix_host_sync_neg.py"),
                           all_functions_paths=())


def test_prefix_cache_host_sync_positive():
    """Prefix-cache idiom gone wrong: host syncs inside the compiled
    block gather/scatter programs (per-admission readbacks of matched
    counts / slab checksums)."""
    res = run_analysis([str(LINT / "serving_prefix_host_sync_pos.py")],
                       checkers=[_prefix_host_sync_checker()],
                       root=str(LINT))
    found = only_rule(res, "host-sync")
    assert len(found) == 4, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert ".item()" in msgs
    assert "float()" in msgs
    assert "device_get" in msgs
    assert "copies a computed value" in msgs


def test_prefix_cache_host_sync_negative():
    """The legal split: host radix walk (numpy keys, refcounts) + pure
    compiled block copies — silent."""
    res = run_analysis([str(LINT / "serving_prefix_host_sync_neg.py")],
                       checkers=[_prefix_host_sync_checker()],
                       root=str(LINT))
    assert res.findings == [], [f.format() for f in res.findings]


def test_serving_recompile_positive():
    """Unbucketed prefill: a fresh jit per arriving prompt length — one
    compiled program per distinct length (jit-in-loop + jit-of-lambda)."""
    res = run_rule("serving_recompile_pos.py", "recompile-hazard")
    found = only_rule(res, "recompile-hazard")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "inside a loop" in msgs
    assert "lambda" in msgs


def test_serving_recompile_negative():
    res = run_rule("serving_recompile_neg.py", "recompile-hazard")
    assert res.findings == [], [f.format() for f in res.findings]


def test_axis_name_positive():
    res = run_rule("axis_name_pos.py", "axis-name")
    found = only_rule(res, "axis-name")
    assert len(found) == 2, [f.format() for f in res.findings]
    assert {"'dp'" in f.message or "'mp'" in f.message
            for f in found} == {True}


def test_axis_name_negative():
    res = run_rule("axis_name_neg.py", "axis-name")
    assert res.findings == [], [f.format() for f in res.findings]


def test_dead_state_positive():
    res = run_rule("dead_state_pos.py", "dead-state")
    found = only_rule(res, "dead-state")
    assert len(found) == 1, [f.format() for f in res.findings]
    assert "_zzq_dead_count" in found[0].message


def test_dead_state_negative():
    res = run_rule("dead_state_neg.py", "dead-state")
    assert res.findings == [], [f.format() for f in res.findings]


def test_registry_drift_positive():
    root = LINT / "registry_pos"
    chk = RegistryDriftChecker(defs_path="defs.py",
                               surfaces={"T": "tensor"}, allowlist={})
    res = run_analysis([str(root)], checkers=[chk], root=str(root))
    found = only_rule(res, "registry-drift")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "T.missing_op" in msgs
    assert "unregistered_public" in msgs


def test_registry_drift_negative():
    root = LINT / "registry_neg"
    chk = RegistryDriftChecker(
        defs_path="defs.py", surfaces={"T": "tensor"},
        allowlist={"allowed_extra": "covered by its own dedicated tests"})
    res = run_analysis([str(root)], checkers=[chk], root=str(root))
    assert res.findings == [], [f.format() for f in res.findings]


# --------------------------------------- ISSUE 4: use-after-donate

def test_use_after_donate_positive():
    """Exactly 3 planted bugs: straight-line read after donation, read
    after a call through a donating-factory attribute, loop-carried
    donation."""
    res = run_rule("use_after_donate_pos.py", "use-after-donate")
    found = only_rule(res, "use-after-donate")
    assert len(found) == 3, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "`buf`" in msgs
    assert "`state`" in msgs         # the self._fn factory pattern
    assert all("donated" in f.message for f in found)


def test_use_after_donate_negative():
    """The engine's legal threading idioms (same-statement rebind,
    attribute-row rebind in a loop, deferred rebind, kwarg donation)
    must stay silent."""
    res = run_rule("use_after_donate_neg.py", "use-after-donate")
    assert res.findings == [], [f.format() for f in res.findings]


# ------------------------------------ ISSUE 4: transitive host-sync

def _transitive_checker():
    return HostSyncChecker(hot_paths=("host_sync_transitive_pos.py",
                                      "host_sync_transitive_neg.py"),
                           all_functions_paths=())


def test_host_sync_transitive_positive():
    """The sink lives in a NON-hot helper; a jitted body reaches it two
    hops down and a scan body one hop down — both call sites fire, with
    the chain and sink location in the message."""
    res = run_analysis([str(LINT / "host_sync_transitive_pos.py")],
                       checkers=[_transitive_checker()], root=str(LINT))
    found = only_rule(res, "host-sync")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "reaches a blocking host sync" in msgs
    assert ".item()" in msgs
    assert "via middle() -> leaf_sync()" in msgs   # the depth-2 chain
    assert "host_sync_transitive_pos.py:15" in msgs  # the sink location


def test_host_sync_transitive_negative():
    """Clean helpers under a jitted body, and a syncing helper reached
    only from host code, stay silent."""
    res = run_analysis([str(LINT / "host_sync_transitive_neg.py")],
                       checkers=[_transitive_checker()], root=str(LINT))
    assert res.findings == [], [f.format() for f in res.findings]


def test_host_sync_transitive_respects_sink_suppression(tmp_path):
    """A sink carrying its own reasoned disable=host-sync is an
    acknowledged sync: it must not taint hot callers with findings that
    could only be silenced far from the source."""
    f = tmp_path / "suppressed_sink.py"
    f.write_text(
        "import jax\n\n"
        "def helper(x):\n"
        "    return x.item()  # graftlint: disable=host-sync -- "
        "intentional one-shot readback\n\n"
        "@jax.jit\n"
        "def hot(x):\n"
        "    return helper(x)\n")
    chk = HostSyncChecker(hot_paths=("suppressed_sink.py",),
                          all_functions_paths=())
    res = run_analysis([str(f)], checkers=[chk], root=str(tmp_path))
    assert res.findings == [], [x.format() for x in res.findings]


# ------------------------------------ ISSUE 4: resource-lifecycle

def test_resource_lifecycle_positive():
    """Exactly 3 planted bugs: a BlockPool row leaked on an exception
    edge, a double free, and an unbalanced refcount pin."""
    res = run_rule("lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 3, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "leaks if an exception fires" in msgs
    assert "double free" in msgs
    assert "refcount pin" in msgs


def test_resource_lifecycle_negative():
    """Protected admission (release in except), try/finally locks,
    immediate hand-off, adjacent alloc/free, balanced pins — silent."""
    res = run_rule("lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_obs_span_pairs_registered():
    """ISSUE 6: the obs tracer's span and capture-session protocols are
    registered ResourcePairs, so the lifecycle rule proves spans close
    on exception edges across the whole scan scope."""
    from paddle_tpu.tools.analysis.checkers.lifecycle import DEFAULT_PAIRS
    pairs = {(p.acquire, p.release) for p in DEFAULT_PAIRS}
    assert ("begin_span", "end_span") in pairs
    assert ("enable", "disable") in pairs
    # PR 26: the engine's step span set (serving.step + the open phase)
    assert ("begin_step", "end_step") in pairs
    hints = {p.acquire: p.receiver_hint for p in DEFAULT_PAIRS}
    # hinted to tracer-ish receivers so `re.match`-style name collisions
    # (or any enable() on a non-tracer object) stay untracked
    assert "tracer" in hints["begin_span"]
    assert "tracer" in hints["enable"]


def test_obs_span_lifecycle_positive():
    """Exactly 3 planted obs leaks: a span leaked on an exception edge,
    a span never ended, and an enable without a guaranteed disable."""
    res = run_rule("obs_lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 3, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "trace span" in msgs
    assert "tracer capture" in msgs
    assert "leaks if an exception fires" in msgs
    assert "never escapes" in msgs


def test_obs_span_lifecycle_negative():
    """try/finally-closed spans/captures, raise-window-free pairs, and
    non-tracer receivers (the hint gate) — silent."""
    res = run_rule("obs_lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_fault_quarantine_pairs_registered():
    """ISSUE 8: the serving fault-injector's enable/disable and the
    watchdog's enter_quarantine/leave_quarantine are registered
    ResourcePairs, receiver-hinted so they never collide with the
    tracer's enable/disable pair (the fault pair sorts FIRST — acquire-
    name collisions resolve first-match by hint)."""
    from paddle_tpu.tools.analysis.checkers.lifecycle import DEFAULT_PAIRS
    triples = {(p.acquire, p.release, p.kind) for p in DEFAULT_PAIRS}
    assert ("enable", "disable", "fault injection") in triples
    assert ("enter_quarantine", "leave_quarantine",
            "quarantine window") in triples
    by_kind = {p.kind: p for p in DEFAULT_PAIRS}
    assert "fault" in by_kind["fault injection"].receiver_hint
    assert "health" in by_kind["quarantine window"].receiver_hint
    # ordering contract: fault pair before the tracer capture pair, so
    # a `faults.enable(...)` receiver is never claimed by the tracer
    # pair (and vice versa — hints are disjoint)
    acquires = [p.kind for p in DEFAULT_PAIRS if p.acquire == "enable"]
    assert acquires.index("fault injection") \
        < acquires.index("tracer capture")


def test_fault_lifecycle_positive():
    """Exactly 3 planted bugs: a fault armed across a raising call
    without protection, a fault armed and never disarmed, and a
    quarantine window leaked on the exception edge."""
    res = run_rule("fault_lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 3, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "fault injection" in msgs
    assert "quarantine window" in msgs
    assert "leaks if an exception fires" in msgs
    assert "never escapes" in msgs


def test_fault_lifecycle_negative():
    """try/finally-protected fault windows and quarantines, adjacent
    arm/disarm, and non-fault receivers (hint gate) — silent."""
    res = run_rule("fault_lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_router_drain_pair_registered():
    """ISSUE 10: the fleet router's drain/undrain is a registered
    ResourcePair (hinted to router receivers), so the lifecycle rule
    proves a drained replica returns to rotation on exception edges."""
    from paddle_tpu.tools.analysis.checkers.lifecycle import DEFAULT_PAIRS
    by_kind = {p.kind: p for p in DEFAULT_PAIRS}
    pair = by_kind["replica drain"]
    assert pair.acquire == "drain" and pair.release == "undrain"
    assert "router" in pair.receiver_hint


def test_router_drain_lifecycle_positive():
    """Exactly 2 planted bugs: a drain leaked across a raising wait
    loop, and a drain never undrained at all."""
    res = run_rule("router_lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "replica drain" in msgs
    assert "leaks if an exception fires" in msgs
    assert "never escapes" in msgs


def test_router_drain_lifecycle_negative():
    """try/finally-protected drains, adjacent drain/undrain, and
    non-router receivers (hint gate) — silent."""
    res = run_rule("router_lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_handoff_and_autoscaler_pairs_registered():
    """ISSUE 13: the disaggregated fleet's KV handoff protocol
    (stage closes with commit OR abort — the first multi-terminal
    pair, via ``alt_release``) and the autoscaler's spawn/retire are
    registered ResourcePairs, receiver-hinted so theatrical ``stage``
    and biological ``spawn`` call sites stay untracked.  The replica
    drain pair additionally accepts permanent ``retire`` as its alt
    release."""
    from paddle_tpu.tools.analysis.checkers.lifecycle import DEFAULT_PAIRS
    by_kind = {p.kind: p for p in DEFAULT_PAIRS}
    handoff = by_kind["kv handoff"]
    assert handoff.acquire == "stage"
    assert handoff.releases == ("commit", "abort")
    assert "handoff" in handoff.receiver_hint
    scaler = by_kind["autoscaled replica"]
    assert scaler.acquire == "spawn" and scaler.release == "retire"
    assert "scaler" in scaler.receiver_hint
    drain = by_kind["replica drain"]
    assert drain.releases == ("undrain", "retire")


def test_handoff_lifecycle_positive():
    """Exactly 2 planted bugs: a staged handoff leaked across a
    raising engine step, and a handoff staged but never committed nor
    aborted."""
    res = run_rule("handoff_lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "kv handoff" in msgs
    assert "leaks if an exception fires" in msgs
    assert "never escapes" in msgs
    assert "commit/abort" in msgs        # both terminals named


def test_handoff_lifecycle_negative():
    """commit-on-success/abort-on-failure windows, adjacent
    stage/abort (the alt release balances), and non-handoff receivers
    (hint gate) — silent."""
    res = run_rule("handoff_lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_autoscaler_lifecycle_positive():
    """Exactly 2 planted bugs: a spawn leaked across a raising wait,
    and a spawn never retired."""
    res = run_rule("autoscaler_lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "autoscaled replica" in msgs
    assert "leaks if an exception fires" in msgs
    assert "never escapes" in msgs


def test_autoscaler_lifecycle_negative():
    """try/finally-protected spawn windows, adjacent spawn/retire, and
    non-scaler receivers (hint gate) — silent."""
    res = run_rule("autoscaler_lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_hedge_pair_registered():
    """ISSUE 15: the hedged-request protocol (issue_hedge closes with
    resolve_hedge — the hedge won — OR purge_hedge — the loser
    unwinds, via ``alt_release``) is a registered ResourcePair,
    receiver-hinted to router receivers so unrelated call sites stay
    untracked."""
    from paddle_tpu.tools.analysis.checkers.lifecycle import DEFAULT_PAIRS
    by_kind = {p.kind: p for p in DEFAULT_PAIRS}
    hedge = by_kind["hedged request"]
    assert hedge.acquire == "issue_hedge"
    assert hedge.releases == ("resolve_hedge", "purge_hedge")
    assert "router" in hedge.receiver_hint


def test_hedge_lifecycle_positive():
    """Exactly 2 planted bugs: an issued hedge leaked across a raising
    fleet step, and a hedge issued but never resolved nor purged."""
    res = run_rule("hedge_lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "hedged request" in msgs
    assert "leaks if an exception fires" in msgs
    assert "never escapes" in msgs
    assert "resolve_hedge/purge_hedge" in msgs   # both terminals named


def test_hedge_lifecycle_negative():
    """resolve-on-win/purge-on-lose windows, adjacent issue/purge (the
    alt release balances), and non-router receivers (hint gate) —
    silent."""
    res = run_rule("hedge_lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_journal_pairs_registered():
    """ISSUE 14: the durable request journal's open/close (crash() —
    the simulated-SIGKILL chaos helper — is a legal alt release) and
    segment begin/seal are registered ResourcePairs, receiver-hinted to
    journal-ish receivers so builtin/file/module ``open`` call sites
    stay untracked.  The hint covers BOTH the factory classmethod
    (``Journal.open``) and bound ``journal`` variables — the release
    arrives as a method on the HANDLE (``journal.close()``), the
    factory-open shape the lifecycle checker matches explicitly."""
    from paddle_tpu.tools.analysis.checkers.lifecycle import DEFAULT_PAIRS
    by_kind = {p.kind: p for p in DEFAULT_PAIRS}
    journal = by_kind["request journal"]
    assert journal.acquire == "open"
    assert journal.releases == ("close", "crash")
    assert "journal" in journal.receiver_hint
    assert "Journal" in journal.receiver_hint
    seg = by_kind["journal segment"]
    assert seg.acquire == "begin_segment"
    assert seg.release == "seal_segment"
    assert "journal" in seg.receiver_hint


def test_journal_lifecycle_positive():
    """Exactly 3 planted bugs: a journal leaked across a raising fleet
    run, a journal never closed, and a begun segment never sealed."""
    res = run_rule("journal_lifecycle_pos.py", "resource-lifecycle")
    found = only_rule(res, "resource-lifecycle")
    assert len(found) == 3, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "request journal" in msgs
    assert "journal segment" in msgs
    assert "leaks if an exception fires" in msgs
    assert "never escapes" in msgs
    assert "close/crash" in msgs         # both terminals named


def test_journal_lifecycle_negative():
    """try/finally-protected open windows, crash() as the alt release,
    adjacent open/close, sealed rotations, and non-journal receivers
    (hint gate; builtin ``open`` has no receiver) — silent."""
    res = run_rule("journal_lifecycle_neg.py", "resource-lifecycle")
    assert res.findings == [], [f.format() for f in res.findings]


def test_resource_pair_registration_api():
    """Custom pairs plug in via the constructor — the documented
    registration API for new alloc/free protocols."""
    from paddle_tpu.tools.analysis.checkers.lifecycle import (
        DEFAULT_PAIRS, ResourceLifecycleChecker, ResourcePair)
    kinds = {p.kind for p in DEFAULT_PAIRS}
    assert "pool slot/row" in kinds and "radix prefix pin" in kinds
    chk = ResourceLifecycleChecker(
        pairs=(ResourcePair("checkout", "checkin", "custom thing"),))
    src = ("def f(store):\n"
           "    h = store.checkout()\n"
           "    x = store.compute(1)\n"
           "    store.checkin(h)\n")
    import paddle_tpu.tools.analysis.walker as W
    ctx = W.FileContext(root=".", path="m.py", relpath="m.py", src=src,
                        tree=ast.parse(src))
    found = chk.check(ctx)
    assert len(found) == 1, [f.format() for f in found]
    assert "custom thing" in found[0].message


# ------------------------------- ISSUE 4: cross-module axis-name

def test_axis_name_cross_module_negative():
    """Axes declared by the imported mesh builder are visible through
    the project index — no suppression needed for sound layering."""
    root = LINT / "axis_cross_neg"
    res = run_analysis([str(root)], root=str(root), rules=["axis-name"])
    assert res.findings == [], [f.format() for f in res.findings]


def test_axis_name_cross_module_positive():
    """An axis NO module in scope declares still fires — exactly once."""
    root = LINT / "axis_cross_pos"
    res = run_analysis([str(root)], root=str(root), rules=["axis-name"])
    found = only_rule(res, "axis-name")
    assert len(found) == 1, [f.format() for f in res.findings]
    assert "'ep'" in found[0].message


# ------------------------------------------- ISSUE 4: project index

def test_project_index_import_and_call_resolution():
    from paddle_tpu.tools.analysis.project import (build_project,
                                                   module_name_for)
    a = ast.parse("def f():\n    return g()\n\ndef g():\n    return 1\n")
    b = ast.parse("from .mod_a import f as alias\n\n"
                  "class C:\n"
                  "    def m(self):\n"
                  "        return self.helper()\n"
                  "    def helper(self):\n"
                  "        return alias()\n")
    proj = build_project([("pkg/mod_a.py", a), ("pkg/mod_b.py", b)])
    fi = proj.resolve_call("pkg.mod_b", "alias")
    assert fi is not None and fi.qname == "pkg.mod_a.f"
    m = proj.resolve_call("pkg.mod_b", "self.helper", cls="C")
    assert m is not None and m.qname == "pkg.mod_b.C.helper"
    helper = proj.modules["pkg.mod_b"].classes["C"].methods["helper"]
    assert [c.qname for c in proj.callees(helper)] == ["pkg.mod_a.f"]
    assert module_name_for("pkg/__init__.py") == ("pkg", True)
    assert module_name_for("__graft_entry__.py") == (
        "__graft_entry__", False)
    assert proj.imported_modules("pkg.mod_b") == {"pkg.mod_a"}
    # plain dotted import: the submodule itself is imported and must be
    # visible to imported_modules (cross-module axis-name relies on it)
    c = ast.parse("import pkg.mod_a\n\ndef h():\n"
                  "    return pkg.mod_a.f()\n")
    proj2 = build_project([("pkg/mod_a.py", a), ("pkg/__init__.py",
                           ast.parse("")), ("user.py", c)])
    assert "pkg.mod_a" in proj2.imported_modules("user")
    fi2 = proj2.resolve_call("user", "pkg.mod_a.f")
    assert fi2 is not None and fi2.qname == "pkg.mod_a.f"


# --------------------------------------- ISSUE 5: graftshape rule families

def test_recompile_shape_positive():
    """Exactly 5 planted fixed-shape violations: bool-mask indexing,
    nonzero, a traced slice bound, a 1-arg where reached through an
    interprocedural summary (chain in the message), and a nonzero
    reached through a ``self.method()`` summary inside a class."""
    res = run_rule("shape_recompile_pos.py", "recompile-shape")
    found = only_rule(res, "recompile-shape")
    assert len(found) == 5, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "boolean-mask" in msgs
    assert "jnp.nonzero()" in msgs
    assert "slice bound" in msgs
    assert "inside _active_rows()" in msgs     # the summary chain
    assert "inside _scatter_rows()" in msgs    # the self-method chain


def test_recompile_shape_negative():
    """3-arg where, size= variants, static slice bounds, shape-derived
    widths, dynamic_slice with static sizes, host code — silent."""
    res = run_rule("shape_recompile_neg.py", "recompile-shape")
    assert res.findings == [], [f.format() for f in res.findings]


def test_recompile_shape_default_hot_paths_cover_serving_and_kernels():
    import fnmatch
    from paddle_tpu.tools.analysis.checkers.shape_recompile import \
        DEFAULT_HOT_PATHS
    for probe in ("paddle_tpu/serving/engine.py",
                  "paddle_tpu/kernels/flash_attention.py"):
        assert any(fnmatch.fnmatch(probe, p) for p in DEFAULT_HOT_PATHS)


def test_dtype_flow_positive():
    """Exactly 5 planted 16-bit accumulation bugs: bf16 sum, bf16 dot
    without preferred_element_type, a narrowing dtype= reduce, a
    down-cast feeding a reduction, and the @-operator contraction."""
    res = run_rule("dtype_flow_pos.py", "dtype-flow")
    found = only_rule(res, "dtype-flow")
    assert len(found) == 5, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "accumulates in bfloat16" in msgs
    assert "preferred_element_type" in msgs
    assert "narrows a float32 operand" in msgs
    assert "down-cast from float32" in msgs
    assert "@ on bfloat16 operands" in msgs


def test_dtype_flow_negative():
    """Widen-before-reduce, dtype=f32 overrides, preferred_element_type,
    unknown dtypes, promoting mixes, storage-only casts — silent."""
    res = run_rule("dtype_flow_neg.py", "dtype-flow")
    assert res.findings == [], [f.format() for f in res.findings]


def test_recompile_shape_through_signature_of_decode_block():
    """ISSUE 7: the decode_block signatures flow ``(y, k_slab', v_slab')``
    through call sites, so fixed-shape hazards on the fused kernel's
    OUTPUTS are provable — exactly 2 planted (bool-mask on the returned
    slab, traced slice bound on the activation)."""
    res = run_rule("shape_recompile_decode_block_pos.py",
                   "recompile-shape")
    found = only_rule(res, "recompile-shape")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "boolean-mask" in msgs
    assert "slice bound" in msgs


def test_recompile_shape_decode_block_negative():
    """The engine's real decode_block usage — fixed-shape triple
    threading, shape-derived reshape, static slices — stays silent."""
    res = run_rule("shape_recompile_decode_block_neg.py",
                   "recompile-shape")
    assert res.findings == [], [f.format() for f in res.findings]


def test_dtype_flow_through_signature_of_decode_block():
    """The decode_block summaries carry the activation dtype onto the
    outputs: exactly 2 planted bf16 accumulation bugs downstream of the
    fused layer (bf16 sum, bf16 @-contraction)."""
    res = run_rule("dtype_flow_decode_block_pos.py", "dtype-flow")
    found = only_rule(res, "dtype-flow")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "accumulates in bfloat16" in msgs
    assert "@ on bfloat16" in msgs


def test_dtype_flow_decode_block_negative():
    res = run_rule("dtype_flow_decode_block_neg.py", "dtype-flow")
    assert res.findings == [], [f.format() for f in res.findings]


def test_recompile_shape_through_decode_block_tp_signature():
    """ISSUE 12: the sharded decode-block signatures flow
    ``(x_s', pk', pv')`` / the ring-matmul outputs through call sites,
    so fixed-shape hazards on the SHARDED kernels' outputs are provable
    — exactly 2 planted (bool-mask on the returned slab shard, traced
    slice bound on the ring-entry output)."""
    res = run_rule("shape_recompile_decode_block_tp_pos.py",
                   "recompile-shape")
    found = only_rule(res, "recompile-shape")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "boolean-mask" in msgs
    assert "slice bound" in msgs


def test_recompile_shape_decode_block_tp_negative():
    """The TP decode body's real sharded-block usage — fixed-shape
    triple threading, static q/k/v column splits of the ring-entry
    output — stays silent."""
    res = run_rule("shape_recompile_decode_block_tp_neg.py",
                   "recompile-shape")
    assert res.findings == [], [f.format() for f in res.findings]


def test_dtype_flow_through_decode_block_tp_signature():
    """The decode_block_tp summaries carry the slot-sharded activation
    dtype onto the outputs: exactly 2 planted bf16 accumulation bugs
    (bf16 sum of the sharded layer output, bf16 @-contraction of the
    ring-exit output)."""
    res = run_rule("dtype_flow_decode_block_tp_pos.py", "dtype-flow")
    found = only_rule(res, "dtype-flow")
    assert len(found) == 2, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "accumulates in bfloat16" in msgs
    assert "@ on bfloat16" in msgs


def test_dtype_flow_decode_block_tp_negative():
    res = run_rule("dtype_flow_decode_block_tp_neg.py", "dtype-flow")
    assert res.findings == [], [f.format() for f in res.findings]


def test_decode_block_tp_module_in_sharding_rule_scope():
    """kernels/decode_block_tp.py drives ppermute rings inside
    shard_map bodies, so the sharding-consistency rule must SCAN it
    clean rather than skip it: its collectives take the axis name as a
    parameter (the caller's contract — serving/tp.py binds 'mp'), so
    the module itself declares no mesh and must carry zero findings
    under the rule."""
    tp_py = REPO_ROOT / "paddle_tpu" / "kernels" / "decode_block_tp.py"
    res = run_analysis([str(tp_py)], root=str(REPO_ROOT),
                       rules=["sharding-consistency"])
    assert res.findings == [], [f.format() for f in res.findings]


def test_dtype_flow_default_hot_paths_cover_kernels_and_optimizer():
    import fnmatch
    from paddle_tpu.tools.analysis.checkers.dtype_flow import \
        DEFAULT_HOT_PATHS
    for probe in ("paddle_tpu/kernels/fused_norm.py",
                  "paddle_tpu/optimizer/adamw.py"):
        assert any(fnmatch.fnmatch(probe, p) for p in DEFAULT_HOT_PATHS)


def test_sharding_consistency_positive():
    """Exactly 3 planted mismatches: unknown mesh axis in a spec, spec
    rank > array rank, collective over an axis the enclosing shard_map
    does not bind."""
    res = run_rule("sharding_pos.py", "sharding-consistency")
    found = only_rule(res, "sharding-consistency")
    assert len(found) == 3, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "'tp'" in msgs
    assert "3 entries" in msgs and "rank 2" in msgs
    assert "only binds ['dp']" in msgs


def test_sharding_consistency_negative():
    res = run_rule("sharding_neg.py", "sharding-consistency")
    assert res.findings == [], [f.format() for f in res.findings]


def test_serving_sharding_positive():
    """ISSUE 9: the rule covers the serving TP idioms — a "mp" serving
    mesh, kv-head slab specs, a shard_map decode body with ring
    collectives — catching exactly the 3 planted mismatches."""
    res = run_rule("serving_sharding_pos.py", "sharding-consistency")
    found = only_rule(res, "sharding-consistency")
    assert len(found) == 3, [f.format() for f in res.findings]
    msgs = " | ".join(f.message for f in found)
    assert "'tp'" in msgs                      # slab spec typo
    assert "2 entries" in msgs and "rank 1" in msgs
    assert "'dp'" in msgs and "only binds ['mp']" in msgs


def test_serving_sharding_negative():
    """The real serving layout (tp.py's idioms) is clean: declared-axis
    specs at the right rank, collectives bound by their shard_map."""
    res = run_rule("serving_sharding_neg.py", "sharding-consistency")
    assert res.findings == [], [f.format() for f in res.findings]


def test_serving_tp_module_in_rule_scope():
    """serving/tp.py is the serving mesh's home module: it constructs
    the Mesh AND carries the slab/bundle P literals, so the rule's
    'mesh visible -> specs checked' gate is ACTIVE over it (a typo'd
    axis there would be a gate failure, not silence)."""
    from paddle_tpu.tools.analysis.checkers.sharding_consistency import \
        _mesh_axes
    import ast
    tp_py = REPO_ROOT / "paddle_tpu" / "serving" / "tp.py"
    axes = _mesh_axes(ast.parse(tp_py.read_text()))
    assert axes == {"mp"}
    res = run_analysis([str(tp_py)], root=str(REPO_ROOT),
                       rules=["sharding-consistency"])
    assert res.findings == [], [f.format() for f in res.findings]


def test_sharding_consistency_no_mesh_module_is_skipped(tmp_path):
    """A module with NO visible mesh CONSTRUCTION never has its specs
    checked — the axes are the caller's contract.  An ``axis_name=``
    parameter default documents an axis but does not make the module the
    mesh's home, so it must not defeat the skip."""
    f = tmp_path / "specs_only.py"
    f.write_text(
        "import jax\n"
        "from jax.sharding import PartitionSpec as P\n\n"
        "def spec_for(param):\n"
        "    return P('anything', 'goes')\n\n"
        "def allreduce(x, axis_name='dp'):\n"
        "    return jax.lax.psum(x, axis_name)\n")
    res = run_analysis([str(f)], root=str(tmp_path),
                       rules=["sharding-consistency"])
    assert res.findings == [], [x.format() for x in res.findings]


# ------------------------------------- ISSUE 5: graftshape infrastructure

def test_signature_table_registration():
    """The documented API: a repo functional registered in the signature
    table participates in shape/dtype propagation — its handler's return
    value flows through the interpreted body."""
    from paddle_tpu.tools.analysis.absint import Arr, Interpreter
    from paddle_tpu.tools.analysis.signatures import (SIGNATURES,
                                                      register_signature)
    name = "zzq_fixture.fused_thing"
    assert name not in SIGNATURES
    register_signature(
        name, lambda interp, rec: rec.args[0].with_(dtype="float32"))
    try:
        fn = ast.parse(
            "def f(x):\n"
            "    import zzq_fixture\n"
            "    y = zzq_fixture.fused_thing(x)\n"
            "    return y\n").body[0]
        interp = Interpreter()
        ret = interp.run(fn, {"x": Arr(traced=True)})
        assert any(r.fname == name for r in interp.calls)
        assert isinstance(ret, Arr) and ret.dtype == "float32" \
            and ret.traced
    finally:
        del SIGNATURES[name]


def test_collective_matmul_signatures_registered():
    """ISSUE 9: the fused compute-collective matmuls carry graftshape
    signatures keyed by definition site, and the handlers propagate the
    TP row blow-up/shrink when tp is concrete."""
    from paddle_tpu.tools.analysis.absint import Arr, Const
    from paddle_tpu.tools.analysis.signatures import SIGNATURES

    class _Rec:
        def __init__(self, args):
            self.args = args
            self.kwargs = {}

    ag = SIGNATURES["paddle_tpu.kernels.collective_matmul"
                    ".allgather_matmul"]
    out = ag(None, _Rec([Arr(shape=(2, 16), dtype="float32",
                             traced=True),
                         Arr(shape=(16, 8), dtype="float32"),
                         Const("mp"), Const(4)]))
    assert out.shape == (8, 8) and out.traced
    rs = SIGNATURES["paddle_tpu.kernels.collective_matmul"
                    ".matmul_reduce_scatter"]
    out = rs(None, _Rec([Arr(shape=(8, 4), dtype="float32",
                             traced=True),
                         Arr(shape=(4, 16), dtype="float32"),
                         Const("mp"), Const(4)]))
    assert out.shape == (2, 16) and out.traced


def test_signature_resolves_through_import_table():
    """A registered repo functional keyed by its DEFINITION-SITE dotted
    name is found even when the call site imports it bare — the
    interpreter rewrites the root through the project import table."""
    from paddle_tpu.tools.analysis.absint import Arr, Interpreter
    from paddle_tpu.tools.analysis.project import build_project
    from paddle_tpu.tools.analysis.signatures import (SIGNATURES,
                                                      register_signature)
    name = "pkgz.ops.fused_zzq"
    register_signature(
        name, lambda interp, rec: rec.args[0].with_(dtype="bfloat16"))
    try:
        ops = ast.parse("def fused_zzq(x):\n    return x\n")
        user = ast.parse("from pkgz.ops import fused_zzq\n\n"
                         "def f(x):\n    return fused_zzq(x)\n")
        proj = build_project([("pkgz/ops.py", ops), ("user.py", user)])
        interp = Interpreter(module_name="user", project=proj)
        ret = interp.run(user.body[1], {"x": Arr(traced=True)})
        assert isinstance(ret, Arr) and ret.dtype == "bfloat16" \
            and ret.traced
    finally:
        del SIGNATURES[name]


def test_repo_kernel_signatures_shipped():
    """The in-tree registrations for the Pallas kernels exist under
    their definition-site names."""
    from paddle_tpu.tools.analysis.signatures import SIGNATURES
    for key in ("paddle_tpu.kernels.flash_attention.flash_attention",
                "paddle_tpu.kernels.flash_attention"
                ".flash_attention_with_lse",
                "paddle_tpu.kernels.fused_norm.fused_rms_norm_pallas",
                "paddle_tpu.kernels.decode_block.decode_block_layer",
                "paddle_tpu.kernels.decode_block.decode_block_attn",
                "paddle_tpu.kernels.decode_block.decode_block_mlp",
                "paddle_tpu.kernels.decode_block.decode_block_reference",
                "paddle_tpu.kernels.decode_block_tp.tp_fused_block_layer",
                "paddle_tpu.kernels.decode_block_tp.decode_block_attn_tp",
                "paddle_tpu.kernels.decode_block_tp.ring_entry_matmul",
                "paddle_tpu.kernels.decode_block_tp.ring_exit_matmul"):
        assert key in SIGNATURES, key


def test_dotted_call_arg_layout():
    """Dotted (non-method) jnp calls must read positional args with the
    function-call layout — the receiver of ``jnp.reshape`` is the MODULE
    (an unknown value, not None), so the method/function split keys on
    the receiver being a known array."""
    from paddle_tpu.tools.analysis.absint import Arr, Interpreter, Tup
    fn = ast.parse(
        "def f():\n"
        "    import jax.numpy as jnp\n"
        "    a = jnp.zeros((4, 8, 2), jnp.float32)\n"
        "    b = jnp.reshape(a, (8, 4, 2))\n"
        "    c = jnp.sum(a)\n"
        "    d = jnp.swapaxes(a, 0, 1)\n"
        "    return (b, c, d)\n").body[0]
    ret = Interpreter().run(fn, {})
    assert isinstance(ret, Tup)
    b, c, d = ret.elts
    assert b.shape == (8, 4, 2), b
    assert c.shape == (), c            # full reduce, not axis=a
    assert d.shape == (8, 4, 2), d


def test_matmul_and_newaxis_shape_folding():
    """1-D matmul operands follow @ semantics (no crash — a bad fold
    here used to IndexError the whole lint run), and x[..., None]
    appends the new axis instead of splicing it mid-shape."""
    from paddle_tpu.tools.analysis.absint import Arr, Interpreter, Tup
    fn = ast.parse(
        "def f():\n"
        "    import jax.numpy as jnp\n"
        "    v = jnp.zeros((8,), jnp.float32)\n"
        "    M = jnp.zeros((8, 4), jnp.float32)\n"
        "    a = v @ M\n"
        "    b = M.T @ v\n"
        "    c = v @ v\n"
        "    d = M[..., None]\n"
        "    return (a, b, c, d)\n").body[0]
    ret = Interpreter().run(fn, {})
    assert isinstance(ret, Tup)
    a, b, c, d = ret.elts
    assert a.shape == (4,), a
    assert b.shape == (4,), b
    assert c.shape == (), c
    assert d.shape == (8, 4, 1), d


def test_abstract_interpreter_shape_and_dtype_propagation():
    """Direct domain check: shapes fold through creation/reshape/matmul,
    dtypes through astype, and traced-ness is viral."""
    from paddle_tpu.tools.analysis.absint import Arr, Interpreter
    fn = ast.parse(
        "def f(x):\n"
        "    import jax.numpy as jnp\n"
        "    a = jnp.zeros((4, 8), jnp.float32)\n"
        "    b = a.reshape(8, 4)\n"
        "    c = a @ b\n"
        "    d = c.astype(jnp.bfloat16)\n"
        "    e = x + d\n"
        "    return e\n").body[0]
    interp = Interpreter()
    ret = interp.run(fn, {"x": Arr(traced=True)})
    assert isinstance(ret, Arr) and ret.traced
    # c = (4,8) @ (8,4) -> (4,4) f32; the astype receiver proves the
    # whole chain folded
    cast = [r for r in interp.calls if r.leaf == "astype"][0]
    assert isinstance(cast.recv, Arr) and cast.recv.shape == (4, 4)
    assert cast.recv.dtype == "float32"


def test_axis_name_module_constant_negative():
    """AXIS = "tp" constants (local, re-exported, and dotted) resolve
    through the project index to declared axes — no finding, where the
    old carve-out skipped them blind."""
    root = LINT / "axis_const_neg"
    res = run_analysis([str(root)], root=str(root), rules=["axis-name"])
    assert res.findings == [], [f.format() for f in res.findings]


def test_axis_name_bare_imported_constant_declares(tmp_path):
    """A mesh built from a BARE from-imported constant (``from axes
    import TP`` then ``Mesh(devs, (TP, "dp"))``) declares that axis —
    declaration- and use-side resolution share the import chain."""
    (tmp_path / "axes.py").write_text('TP = "tp"\n')
    (tmp_path / "user.py").write_text(
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "from axes import TP\n\n"
        "def build(devices):\n"
        "    return Mesh(np.array(devices), (TP, 'dp'))\n\n"
        "def allreduce(x):\n"
        "    return jax.lax.psum(x, 'tp')\n")
    res = run_analysis([str(tmp_path)], root=str(tmp_path),
                       rules=["axis-name"])
    assert res.findings == [], [f.format() for f in res.findings]


def test_axis_name_module_constant_positive():
    """A constant naming an axis NO module declares fires — once for the
    bare use, once more through a mixed ("literal", CONST) tuple, whose
    declared half stays silent."""
    root = LINT / "axis_const_pos"
    res = run_analysis([str(root)], root=str(root), rules=["axis-name"])
    found = only_rule(res, "axis-name")
    assert len(found) == 2, [f.format() for f in res.findings]
    assert all("'ep'" in f.message for f in found)


# ------------------------------------------------------------ suppression

def test_suppression_with_reason_moves_finding_to_suppressed():
    res = run_rule("suppress_ok.py", "tracer-leak")
    assert res.findings == [], [f.format() for f in res.findings]
    assert [f.rule for f in res.suppressed] == ["tracer-leak"]


def test_suppression_without_reason_is_itself_a_finding():
    res = run_rule("suppress_bad.py", "tracer-leak")
    rules = sorted(f.rule for f in res.findings)
    assert rules == ["bad-suppression", "tracer-leak"], \
        [f.format() for f in res.findings]
    assert res.suppressed == []


def test_disable_next_and_disable_file_forms():
    src = ("# graftlint: disable-file=axis-name -- caller threads the mesh\n"
           "# graftlint: disable-next=host-sync,tracer-leak -- init readback\n"
           "x = 1\n")
    sup = parse_suppressions("f.py", src)
    assert not sup.errors
    assert sup.file_wide == {"axis-name"}
    assert sup.by_line[3] == {"host-sync", "tracer-leak"}
    assert sup.matches(Finding("axis-name", "f.py", 99, 0, "m"))
    assert sup.matches(Finding("host-sync", "f.py", 3, 0, "m"))
    assert not sup.matches(Finding("host-sync", "f.py", 4, 0, "m"))


def test_disable_all_matches_every_rule():
    sup = parse_suppressions(
        "f.py", "y = bad()  # graftlint: disable=all -- generated code\n")
    assert sup.matches(Finding("anything", "f.py", 1, 0, "m"))


def test_directive_inside_string_literal_is_ignored():
    src = 's = "# graftlint: disable=tracer-leak"\n'
    sup = parse_suppressions("f.py", src)
    assert not sup.by_line and not sup.file_wide and not sup.errors


def test_suppression_reason_may_contain_double_dash():
    """The ``--`` separator binds at the FIRST occurrence; the reason
    keeps any later ones verbatim."""
    sup = parse_suppressions(
        "f.py", "x = 1  # graftlint: disable=host-sync -- host data "
                "-- not device -- by design\n")
    assert not sup.errors
    assert sup.by_line[1] == {"host-sync"}
    assert sup.matches(Finding("host-sync", "f.py", 1, 0, "m"))


def test_suppression_multi_rule_file_and_next_stacking():
    """disable-file and disable-next stack: a finding on the covered
    line matches through EITHER; other rules on other lines do not."""
    src = ("# graftlint: disable-file=axis-name -- mesh is caller-owned\n"
           "# graftlint: disable-next=host-sync,use-after-donate -- "
           "one-shot init readback\n"
           "x = f()\n"
           "y = g()\n")
    sup = parse_suppressions("f.py", src)
    assert not sup.errors
    assert sup.matches(Finding("axis-name", "f.py", 3, 0, "m"))
    assert sup.matches(Finding("host-sync", "f.py", 3, 0, "m"))
    assert sup.matches(Finding("use-after-donate", "f.py", 3, 0, "m"))
    assert not sup.matches(Finding("host-sync", "f.py", 4, 0, "m"))
    assert sup.matches(Finding("axis-name", "f.py", 4, 0, "m"))
    assert len(sup.directives) == 2


# -------------------------------------------------------- the CI gate

def test_repo_is_lint_clean():
    """THE contract: zero unsuppressed findings over the default scope
    (library + entry + scripts) — every live finding must be
    fixed or carry a reasoned suppression.  Shares the CLI's parse cache
    (cheap here, and it exercises the cache read path in-process)."""
    res = run_analysis(GATE_SCOPE, root=str(REPO_ROOT),
                       project_paths=GATE_SCOPE,
                       cache_path=str(REPO_ROOT / ".graftlint_cache"
                                      / "parse.pkl"))
    assert res.findings == [], "graftlint regressions:\n" + \
        "\n".join(f.format() for f in res.findings)
    assert res.files_scanned > 200    # the walk really covered the tree


def test_cli_exits_zero_and_reports_json():
    proc = subprocess.run(
        [sys.executable, "scripts/graftlint.py", "--json"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["ok"] is True
    assert data["findings"] == []


def test_cli_changed_flow_exits_clean():
    """The pre-commit invocation: --since HEAD lints only the working
    set (possibly empty) against the full project index."""
    proc = subprocess.run(
        [sys.executable, "scripts/graftlint.py", "--since", "HEAD"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_sarif_output_schema_smoke():
    """--sarif emits structurally valid SARIF 2.1.0 for a fixture with
    known findings (3 planted lifecycle bugs)."""
    proc = subprocess.run(
        [sys.executable, "scripts/graftlint.py", "--sarif",
         "--rule", "resource-lifecycle",
         "tests/fixtures/lint/lifecycle_pos.py"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "resource-lifecycle" in rule_ids
    results = [r for r in run["results"] if "suppressions" not in r]
    assert len(results) == 3
    for r in results:
        assert r["ruleId"] == "resource-lifecycle"
        assert r["level"] == "error"
        assert r["message"]["text"]
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("lifecycle_pos.py")
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1


def test_sarif_covers_graftshape_rules():
    """--sarif over the three graftshape fixture positives: structurally
    valid SARIF 2.1.0 with all three rule ids and the exact planted
    finding counts (5 + 5 + 3)."""
    proc = subprocess.run(
        [sys.executable, "scripts/graftlint.py", "--sarif",
         "--rule", "recompile-shape", "--rule", "dtype-flow",
         "--rule", "sharding-consistency",
         "tests/fixtures/lint/shape_recompile_pos.py",
         "tests/fixtures/lint/dtype_flow_pos.py",
         "tests/fixtures/lint/sharding_pos.py"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    run = doc["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"recompile-shape", "dtype-flow",
            "sharding-consistency"} <= rule_ids
    live = [r for r in run["results"] if "suppressions" not in r]
    by_rule = {}
    for r in live:
        by_rule.setdefault(r["ruleId"], []).append(r)
        assert r["message"]["text"]
        loc = r["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
    assert len(by_rule["recompile-shape"]) == 5
    assert len(by_rule["dtype-flow"]) == 5
    assert len(by_rule["sharding-consistency"]) == 3
    levels = {r["level"] for r in live}
    assert levels == {"error", "warning"}   # dtype-flow warns, rest error


# ---------------------------------------------- graftprog (ISSUE 16)

def test_compile_surface_positive():
    """Exactly the four planted findings: unbounded DYN body, unbounded
    data-dependent static arg (both errors), jit-in-loop growth and a
    dead program (both warnings) — each carrying its derived key space
    in the finding props."""
    res = run_rule("compile_surface_pos.py", "compile-surface")
    found = only_rule(res, "compile-surface")
    assert len(found) == 4, [f.format() for f in res.findings]
    errors = [f for f in found if f.severity == "error"]
    warns = [f for f in found if f.severity == "warning"]
    assert len(errors) == 2 and len(warns) == 2
    msgs = " | ".join(f.message for f in found)
    assert "unbounded static-key space" in msgs
    assert "inside a loop" in msgs
    assert "dead program" in msgs
    for f in found:
        props = dict(f.props)
        assert props["unit"].startswith("compile_surface_pos:")
        assert props["key_space"] in {"trace-static", "bucketed",
                                      "unbounded"}
    assert {dict(f.props)["key_space"] for f in errors} == {"unbounded"}


def test_compile_surface_negative():
    """The pinned-engine idiom (memoized factory jits, bucket-producer
    shapes, rooted class) stays silent."""
    res = run_rule("compile_surface_neg.py", "compile-surface")
    assert res.findings == [], [f.format() for f in res.findings]


# ------------------------------------------ spec fixtures (ISSUE 18)

def test_spec_compile_surface_positive():
    """The speculative anti-patterns: a ragged verify keyed on the
    host draft length (unbounded static-key space — error), a per-slot
    verify jit in the loop and an unrooted verify unit (warnings)."""
    res = run_rule("spec_pos.py", "compile-surface")
    found = only_rule(res, "compile-surface")
    assert len(found) == 3, [f.format() for f in res.findings]
    errors = [f for f in found if f.severity == "error"]
    warns = [f for f in found if f.severity == "warning"]
    assert len(errors) == 1 and len(warns) == 2
    msgs = " | ".join(f.message for f in found)
    assert "unbounded static-key space" in msgs
    assert "inside a loop" in msgs
    assert "dead program" in msgs
    assert {dict(f.props)["key_space"] for f in errors} == {"unbounded"}


def test_spec_compile_surface_negative():
    """The engine's actual speculative idiom — pure-host draft table,
    ONE memoized fixed-shape verify with a trace-counter tick, decode
    as the named fallback — stays silent."""
    res = run_rule("spec_neg.py", "compile-surface")
    assert res.findings == [], [f.format() for f in res.findings]


def test_memory_budget_positive():
    """ISSUE 19: every leg of the memory-budget rule fires exactly once
    on the planted fixture — 3 errors (VMEM over budget, whole-slab
    upcast, dequantized-weight materialization) + 2 warnings
    (non-capacity pool extent, unbounded append)."""
    from paddle_tpu.tools.analysis import ERROR, WARNING
    res = run_rule("memory_pos.py", "memory-budget")
    found = only_rule(res, "memory-budget")
    assert len(found) == 5, [f.format() for f in found]
    sev = sorted(f.severity for f in found)
    assert sev == sorted([ERROR, ERROR, ERROR, WARNING, WARNING])
    msgs = " | ".join(f.message for f in found)
    assert "VMEM plan 'plan_decode_block' exceeds" in msgs
    assert "full-size upcast copy of pool slab '.ks'" in msgs
    assert "full-size dequantized weight" in msgs
    assert "do not flow from registered capacity fields" in msgs
    assert "unbounded append inside `while True`" in msgs
    # every memory finding carries the byte-evidence property triple
    for f in found:
        props = dict(f.props)
        assert props.get("bytes") and props.get("budget") \
            and props.get("unit"), f.format()


def test_memory_budget_negative():
    """The blessed forms: capacity-clean pool (including a
    module-registered field), tile reads, scale-after-dot, bounded
    append, a plan that fits its real budget — zero findings."""
    res = run_rule("memory_neg.py", "memory-budget")
    assert res.findings == [], [f.format() for f in res.findings]


def test_sarif_memory_budget_properties():
    """Satellite (ISSUE 19): memory-budget SARIF results carry
    ``properties.{bytes,budget,unit}`` — CI annotators can show the
    byte evidence inline."""
    proc = subprocess.run(
        [sys.executable, "scripts/graftlint.py", "--sarif",
         "--rule", "memory-budget",
         "tests/fixtures/lint/memory_pos.py"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    run = doc["runs"][0]
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "memory-budget" in rules
    live = [r for r in run["results"] if "suppressions" not in r]
    assert len(live) == 5
    assert sorted(r["level"] for r in live) == \
        ["error", "error", "error", "warning", "warning"]
    for r in live:
        for key in ("bytes", "budget", "unit"):
            assert r["properties"].get(key), (key, r)


def test_cli_memory_manifest_deterministic_and_pinned():
    """Tentpole artifact (ISSUE 19): ``--memory`` emits byte-identical
    JSON across runs, and the capacity claims hold — both pools derive
    capacity-clean formulas, every registered VMEM plan fits its
    declared budget at every reference tiling, the KV tier's
    bytes-per-block halves from bf16 to int8, and the EngineCore plane
    is provably fixed-footprint (no allocation outside the init/rebuild
    owners)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "scripts/graftlint.py", "--memory"]
    a = subprocess.run(cmd, cwd=str(REPO_ROOT), capture_output=True,
                       text=True, timeout=600, env=env)
    b = subprocess.run(cmd, cwd=str(REPO_ROOT), capture_output=True,
                       text=True, timeout=600, env=env)
    assert a.returncode == 0, a.stdout + a.stderr
    assert b.returncode == 0, b.stdout + b.stderr
    assert a.stdout == b.stdout      # deterministic artifact
    m = json.loads(a.stdout)
    assert m["graftmem_version"] == 1
    pools = m["pools"]
    assert {"paddle_tpu.serving.kv_pool.KVPool",
            "paddle_tpu.serving.kv_pool.BlockPool"} <= set(pools)
    for p in pools.values():
        assert p["capacity_ok"], p
        assert p["bytes_at_reference"] > 0
    # the slab formulas carry the symbolic element size — the int8 KV
    # ladder is derived by re-evaluating them, not by re-measuring
    assert "itemsize" in pools[
        "paddle_tpu.serving.kv_pool.KVPool"]["formula"]
    kv = m["kv_tier"]
    assert kv["bytes_per_block"]["bfloat16"] == \
        2 * kv["bytes_per_block"]["int8"]
    for chip, row in kv["max_resident_blocks"].items():
        assert row["int8"] >= row["bfloat16"], chip
    assert m["vmem"]["all_ok"], m["vmem"]
    assert {"plan_decode_block", "plan_decode_block_tp"} <= \
        set(m["vmem"]["plans"])
    for plan in m["vmem"]["plans"].values():
        assert plan["ok"] and plan["tilings"], plan
    plane = m["planes"]["paddle_tpu.serving.engine.EngineCore"]
    assert plane["fixed_footprint"], plane["alloc_sites"]
    assert all(s["allowed"] for s in plane["alloc_sites"])
    # per-program footprints carry evidence legs and donation notes
    assert m["programs"]
    for p in m["programs"]:
        assert p["peak_bytes"] == sum(p["legs"].values())
        assert set(p["legs"]) == {"weights", "pools", "row_state",
                                  "staging", "activations"}
    donated = {p["counter"]: p["donated"] for p in m["programs"]}
    assert donated["decode"] is True     # donation: slabs counted once


def test_cli_manifest_deterministic_and_pinned():
    """``--manifest`` emits byte-identical JSON across runs, and the
    EngineCore plane IS the pinned program set: bucketed prefill + ONE
    decode + 1 gather + 1 scatter (the compile pin, proved statically)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "scripts/graftlint.py", "--manifest"]
    a = subprocess.run(cmd, cwd=str(REPO_ROOT), capture_output=True,
                       text=True, timeout=600, env=env)
    b = subprocess.run(cmd, cwd=str(REPO_ROOT), capture_output=True,
                       text=True, timeout=600, env=env)
    assert a.returncode == 0, a.stdout + a.stderr
    assert b.returncode == 0, b.stdout + b.stderr
    assert a.stdout == b.stdout      # deterministic artifact
    m = json.loads(a.stdout)
    assert m["graftprog_version"] == 1
    plane = m["planes"]["paddle_tpu.serving.engine.EngineCore"]
    assert set(plane) == {"prefill", "decode", "verify", "gather",
                          "scatter"}
    assert plane["decode"]["upper_bound"] == "1"
    assert plane["verify"]["upper_bound"] == "1"
    assert plane["gather"]["upper_bound"] == "1"
    assert plane["scatter"]["upper_bound"] == "1"
    assert plane["prefill"]["key_space"] == "bucketed"
    # the two decode VARIANTS (composed + fused) share one holder slot;
    # same for the two verify variants (composed + tp shard_map)
    assert plane["decode"]["holders"] == ["_decode_fn"]
    assert plane["verify"]["holders"] == ["_verify_fn"]
    # schema smoke over every program record (satellite: --manifest is
    # covered next to the SARIF smoke)
    assert m["programs"], "empty program list"
    for p in m["programs"]:
        assert p["kind"] in {"jit", "shard_map", "pallas_call",
                             "aot-export"}
        assert p["key"]["class"] in {"bucketed", "trace-static",
                                     "unbounded"}
        assert p["key"]["upper_bound"]
        assert isinstance(p["line"], int) and p["line"] >= 1
        assert p["path"].endswith(".py")
        assert p["id"].count(":") == 2
    kinds = {p["kind"] for p in m["programs"]}
    assert {"jit", "shard_map", "pallas_call", "aot-export"} <= kinds
    # every registered entry point made it into the manifest header
    assert "paddle_tpu.serving.engine.EngineCore.step" \
        in m["entry_points"]["roots"] or any(
            q.startswith("paddle_tpu.serving.engine.EngineCore.")
            for q in m["entry_points"]["roots"])


def test_sarif_compile_surface_properties():
    """compile-surface SARIF results carry the derived key space in the
    property bag and the rule carries driver metadata."""
    proc = subprocess.run(
        [sys.executable, "scripts/graftlint.py", "--sarif",
         "--rule", "compile-surface",
         "tests/fixtures/lint/compile_surface_pos.py"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    rules = {r["id"]: r for r in run["tool"]["driver"]["rules"]}
    assert "compile-surface" in rules
    assert "compile pin" in rules["compile-surface"][
        "shortDescription"]["text"]
    live = [r for r in run["results"] if "suppressions" not in r]
    assert len(live) == 4
    levels = sorted(r["level"] for r in live)
    assert levels == ["error", "error", "warning", "warning"]
    for r in live:
        assert r["properties"]["key_space"] in {
            "trace-static", "bucketed", "unbounded"}
        unit_mod = r["properties"]["unit"].split(":")[0]
        assert unit_mod.endswith("compile_surface_pos")


def test_cache_version_tracks_signature_and_entry_tables():
    """Satellite (ISSUE 16): the parse-cache version must move when the
    registered signatures or entry points change — the pre-PR cache
    could serve cross-module results derived under stale tables."""
    from paddle_tpu.tools.analysis import (register_entry_point,
                                           register_signature)
    from paddle_tpu.tools.analysis.entrypoints import _EXTRA_ENTRY_POINTS
    from paddle_tpu.tools.analysis.signatures import SIGNATURES
    from paddle_tpu.tools.analysis.walker import _cache_version
    v0 = _cache_version()
    register_signature("zz_cache_probe_sig", lambda interp, rec: None)
    try:
        assert _cache_version() != v0
    finally:
        SIGNATURES.pop("zz_cache_probe_sig")
    assert _cache_version() == v0
    register_entry_point("zz.cache.probe_entry")
    try:
        assert _cache_version() != v0
    finally:
        _EXTRA_ENTRY_POINTS.remove("zz.cache.probe_entry")
    assert _cache_version() == v0


def test_cache_version_tracks_memory_tables():
    """Satellite (ISSUE 19): registering a byte signature or a capacity
    field moves the parse-cache version — cached results derived under
    the old byte-accounting tables must never be served."""
    from paddle_tpu.tools.analysis import (register_byte_signature,
                                           register_capacity_field)
    from paddle_tpu.tools.analysis.memory import (_EXTRA_BYTE_SIGNATURES,
                                                  _EXTRA_CAPACITY_FIELDS)
    from paddle_tpu.tools.analysis.walker import _cache_version
    v0 = _cache_version()
    register_byte_signature("zz.probe_alloc", "prod(shape) * itemsize")
    try:
        assert _cache_version() != v0
    finally:
        _EXTRA_BYTE_SIGNATURES.pop("zz.probe_alloc")
    assert _cache_version() == v0
    register_capacity_field("zz_probe_depth")
    try:
        assert _cache_version() != v0
    finally:
        _EXTRA_CAPACITY_FIELDS.remove("zz_probe_depth")
    assert _cache_version() == v0


def test_stale_cache_not_served_after_entry_point_change(tmp_path):
    """End-to-end: a saved parse cache is NOT loaded once the entry-point
    table differs from the one it was written under."""
    from paddle_tpu.tools.analysis import register_entry_point
    from paddle_tpu.tools.analysis.entrypoints import _EXTRA_ENTRY_POINTS
    from paddle_tpu.tools.analysis.walker import _ParseCache, _parse_files
    f = tmp_path / "m.py"
    f.write_text("x = 1\n")
    cache_path = str(tmp_path / "cache.pkl")
    c1 = _ParseCache(cache_path)
    _parse_files([str(f)], str(tmp_path), c1)
    c1.save()
    assert _ParseCache(cache_path).entries    # same tables: served
    register_entry_point("zz.stale.probe")
    try:
        assert not _ParseCache(cache_path).entries   # stale: dropped
    finally:
        _EXTRA_ENTRY_POINTS.remove("zz.stale.probe")
    assert _ParseCache(cache_path).entries    # tables restored: served


def test_surface_build_skipped_for_inert_files(tmp_path):
    """Satellite (ISSUE 16): a changed-file lint only pays for surface
    construction when the file can actually host a compile unit or a
    root marker — the checker's token gate keeps ``--changed`` runs over
    inert files free of the graftprog pass."""
    from paddle_tpu.tools.analysis import compile_surface as cs
    inert = tmp_path / "compile_surface_inert.py"   # hot glob, no tokens
    inert.write_text("def f():\n    return 1\n")
    before = cs.BUILD_COUNT
    run_analysis([str(inert)], root=str(tmp_path),
                 rules=["compile-surface"])
    assert cs.BUILD_COUNT == before, \
        "surface built for a file that cannot hold a compile unit"
    probe = tmp_path / "compile_surface_probe.py"
    probe.write_text("import jax\n\n\ndef g(x):\n"
                     "    return jax.jit(lambda y: y + 1)(x)\n")
    run_analysis([str(probe)], root=str(tmp_path),
                 rules=["compile-surface"])
    assert cs.BUILD_COUNT == before + 1


def test_memory_surface_build_skipped_for_inert_files(tmp_path):
    """Satellite (ISSUE 19): the memory-budget token gate mirrors the
    compile-surface one — an inert file on the hot globs never pays for
    memory-surface construction in a ``--changed`` run."""
    from paddle_tpu.tools.analysis import memory as gm
    inert = tmp_path / "memory_inert.py"   # hot glob, no tokens
    inert.write_text("def f():\n    return 1\n")
    before = gm.BUILD_COUNT
    run_analysis([str(inert)], root=str(tmp_path),
                 rules=["memory-budget"])
    assert gm.BUILD_COUNT == before, \
        "memory surface built for a file with no memory tokens"
    probe = tmp_path / "memory_probe.py"
    probe.write_text(
        "import jax.numpy as jnp\n\n\nclass ProbePool:\n"
        "    def __init__(self, num_slots):\n"
        "        self.ks = jnp.zeros((num_slots, 4), jnp.float32)\n")
    run_analysis([str(probe)], root=str(tmp_path),
                 rules=["memory-budget"])
    assert gm.BUILD_COUNT == before + 1


# ------------------------------------------- collective-order (ISSUE 20)

def test_collective_order_positive():
    """ISSUE 20: every error leg of the collective-order rule fires
    exactly once on the planted fixture — divergent `if`, divergent
    `while`, non-permutation table, fused/composed schedule drift, and
    an axis the binding shard_map never declares."""
    from paddle_tpu.tools.analysis import ERROR
    res = run_rule("comm_pos.py", "collective-order")
    found = only_rule(res, "collective-order")
    assert len(found) == 5, [f.format() for f in found]
    assert all(f.severity == ERROR for f in found)
    msgs = " | ".join(f.message for f in found)
    assert "value-divergent `if`" in msgs
    assert "`while` loop" in msgs
    assert "not a permutation" in msgs
    assert "hop-equivalent" in msgs
    assert "never exists inside this program" in msgs
    # every comm finding carries the schedule-evidence property bag
    for f in found:
        props = dict(f.props)
        assert props.get("op") and props.get("hops"), f.format()


def test_collective_order_negative():
    """The blessed forms: guarded neighbour ring, declared seam marker,
    shard_map body reducing over the axis the program binds — zero
    findings."""
    res = run_rule("comm_neg.py", "collective-order")
    assert res.findings == [], [f.format() for f in res.findings]


def test_collective_order_unregistered_module_warns(tmp_path):
    """A module that issues collectives without being a registered comm
    module and without a ``__remote_dma_seams__`` marker gets exactly
    one WARNING (at its first collective site)."""
    from paddle_tpu.tools.analysis import WARNING
    probe = tmp_path / "comm_probe.py"
    probe.write_text(
        "import jax\n\n\ndef ring(x, axis_name, tp):\n"
        "    perm = [(i, (i + 1) % tp) for i in range(tp)]\n"
        "    return jax.lax.ppermute(x, axis_name, perm)\n")
    res = run_analysis([str(probe)], root=str(tmp_path),
                       rules=["collective-order"])
    found = only_rule(res, "collective-order")
    assert len(found) == 1, [f.format() for f in found]
    assert found[0].severity == WARNING
    assert "__remote_dma_seams__" in found[0].message


def test_sarif_collective_order_properties():
    """Satellite (ISSUE 20): collective-order SARIF results carry
    ``properties.{op,axis,bytes,hops}`` — CI annotators can show the
    schedule evidence inline."""
    proc = subprocess.run(
        [sys.executable, "scripts/graftlint.py", "--sarif",
         "--rule", "collective-order",
         "tests/fixtures/lint/comm_pos.py"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    run = doc["runs"][0]
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "collective-order" in rules
    live = [r for r in run["results"] if "suppressions" not in r]
    assert len(live) == 5
    assert all(r["level"] == "error" for r in live)
    for r in live:
        assert r["properties"].get("op"), r
        assert r["properties"].get("hops"), r


def test_cli_comm_manifest_deterministic_and_pinned():
    """Tentpole artifact (ISSUE 20): ``--comm`` emits byte-identical
    JSON across runs, both ring drivers' ppermute seams are enumerated
    with per-hop payload bytes at the flagship reference env, the fused
    (Pallas) and composed (XLA) TP decode paths are hop-equivalent, and
    the order-safety proof holds over the whole scan scope."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "scripts/graftlint.py", "--comm"]
    a = subprocess.run(cmd, cwd=str(REPO_ROOT), capture_output=True,
                       text=True, timeout=600, env=env)
    b = subprocess.run(cmd, cwd=str(REPO_ROOT), capture_output=True,
                       text=True, timeout=600, env=env)
    assert a.returncode == 0, a.stdout + a.stderr
    assert b.returncode == 0, b.stdout + b.stderr
    assert a.stdout == b.stdout      # deterministic artifact
    m = json.loads(a.stdout)
    assert m["graftcomm_version"] == 1
    assert m["order_safety"]["ok"], m["order_safety"]
    seams = m["seams"]
    # the two ring-driver families: composed XLA + fused Pallas seam
    # sites, each with a payload ladder at the reference env
    assert {"paddle_tpu.kernels.collective_matmul.allgather_matmul",
            "paddle_tpu.kernels.collective_matmul.matmul_reduce_scatter",
            "paddle_tpu.kernels.decode_block_tp.ring_entry_matmul",
            "paddle_tpu.kernels.decode_block_tp.ring_exit_matmul"} \
        <= set(seams)
    # the travelling activation shard [num_slots/tp, hidden] at bf16:
    # 8/tp * 768 * 2 bytes per hop
    for q in ("paddle_tpu.kernels.collective_matmul.allgather_matmul",
              "paddle_tpu.kernels.decode_block_tp.ring_entry_matmul"):
        assert seams[q]["per_hop_payload_bytes"] == {
            "tp=2": 6144, "tp=4": 3072, "tp=8": 1536}, q
        assert seams[q]["ppermute_sites"], q
    roles = m["roles"]
    assert roles["entry"]["equivalent"] and roles["exit"]["equivalent"]
    assert roles["entry"]["signature"] == ["ppermute:tp-1:neighbor"]
    # the manifest names the programs each seam rides in: the TP decode
    # and verify shard_maps, with every collective's axis resolved to
    # the binding mesh axis
    progs = m["programs"]
    assert any(p["body"] == "paddle_tpu.serving.tp._tp_decode_body"
               for p in progs.values())
    assert any(p["body"] == "paddle_tpu.serving.tp._tp_verify_body"
               for p in progs.values())
    for p in progs.values():
        for s in p["schedule"]:
            assert s["axis"] == "mp", s
    entry_seam = seams[
        "paddle_tpu.kernels.collective_matmul.allgather_matmul"]
    assert entry_seam["programs"], "seam not attributed to any program"
    # ring mirror: the integer walk tables for every reference tp
    assert set(m["ring_mirror"]) == {"tp=2", "tp=4", "tp=8"}
    for row in m["ring_mirror"].values():
        assert row["is_permutation"]
    # fused and composed layer paths traverse the same role sequence
    lp = m["layer_paths"]
    assert lp["paddle_tpu.serving.tp._tp_layer"]["roles"] == \
        lp["paddle_tpu.kernels.decode_block_tp.tp_fused_block_layer"][
            "roles"] == ["entry", "exit", "entry", "exit"]


def test_cache_version_tracks_comm_modules():
    """Satellite (ISSUE 20): registering a comm module moves the
    parse-cache version — cached results derived under the old comm
    tables must never be served."""
    from paddle_tpu.tools.analysis import register_comm_module
    from paddle_tpu.tools.analysis.comm import _EXTRA_COMM_MODULES
    from paddle_tpu.tools.analysis.walker import _cache_version
    v0 = _cache_version()
    register_comm_module("zz.probe_comm")
    try:
        assert _cache_version() != v0
    finally:
        _EXTRA_COMM_MODULES.remove("zz.probe_comm")
    assert _cache_version() == v0


def test_comm_surface_build_skipped_for_inert_files(tmp_path):
    """Satellite (ISSUE 20): the collective-order token gate mirrors the
    compile-surface one — an inert file on the hot globs never pays for
    comm-surface construction in a ``--changed`` run."""
    from paddle_tpu.tools.analysis import comm as gc
    inert = tmp_path / "comm_inert.py"   # hot glob, no tokens
    inert.write_text("def f():\n    return 1\n")
    before = gc.BUILD_COUNT
    run_analysis([str(inert)], root=str(tmp_path),
                 rules=["collective-order"])
    assert gc.BUILD_COUNT == before, \
        "comm surface built for a file with no collective tokens"
    probe = tmp_path / "comm_live.py"
    probe.write_text(
        "import jax\n\n__remote_dma_seams__ = {}\n\n\n"
        "def g(x, axis_name):\n"
        "    return jax.lax.psum(x, axis_name)\n")
    run_analysis([str(probe)], root=str(tmp_path),
                 rules=["collective-order"])
    assert gc.BUILD_COUNT == before + 1


def test_scan_performance_budget_with_warm_cache():
    """Full-scope scan must stay pre-commit-viable: one timed run under
    a generous wall-clock bound (catches accidental O(files^2)
    regressions, not jitter).  The parse cache is warm here — the CLI
    tests above populate it; the bound absorbs a cold standalone run.
    ISSUE 16: the budget now covers graftprog too — the lint pass builds
    the compile surface (serving/kernels are hot paths) AND a full
    ``--manifest`` emission rides inside the same 90s pin.  ISSUE 19
    adds graftmem: the ``--memory`` capacity-manifest emission rides
    inside the SAME budget — byte accounting must stay pre-commit
    cheap.  ISSUE 20 adds graftcomm: the ``--comm`` seam-manifest
    emission rides inside the same 90s pin too."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "scripts/graftlint.py"]
    t0 = time.perf_counter()
    timed = subprocess.run(cmd, cwd=str(REPO_ROOT), capture_output=True,
                           text=True, timeout=600, env=env)
    dt = time.perf_counter() - t0
    assert timed.returncode == 0, timed.stdout + timed.stderr
    assert (REPO_ROOT / ".graftlint_cache" / "parse.pkl").exists()
    t1 = time.perf_counter()
    man = subprocess.run(cmd + ["--manifest"], cwd=str(REPO_ROOT),
                         capture_output=True, text=True, timeout=600,
                         env=env)
    dt_man = time.perf_counter() - t1
    assert man.returncode == 0, man.stdout + man.stderr
    json.loads(man.stdout)    # still a valid artifact under timing
    t2 = time.perf_counter()
    mem = subprocess.run(cmd + ["--memory"], cwd=str(REPO_ROOT),
                         capture_output=True, text=True, timeout=600,
                         env=env)
    dt_mem = time.perf_counter() - t2
    assert mem.returncode == 0, mem.stdout + mem.stderr
    json.loads(mem.stdout)    # still a valid artifact under timing
    t3 = time.perf_counter()
    comm = subprocess.run(cmd + ["--comm"], cwd=str(REPO_ROOT),
                          capture_output=True, text=True, timeout=600,
                          env=env)
    dt_comm = time.perf_counter() - t3
    assert comm.returncode == 0, comm.stdout + comm.stderr
    json.loads(comm.stdout)   # still a valid artifact under timing
    assert dt + dt_man + dt_mem + dt_comm < 90.0, (
        f"warm full-scope scan + manifests took {dt:.1f}s + "
        f"{dt_man:.1f}s + {dt_mem:.1f}s + {dt_comm:.1f}s (budget 90s)")
