"""Speculative decoding (serving/spec.py + engine verify path, ISSUE 18).

The load-bearing contracts:

  * TOKEN-FOR-TOKEN parity between a ``spec_k=0`` engine and a
    speculating engine on the same workload — greedy AND seeded, across
    tp=1 composed, tp=1 fused (Pallas decode block) and tp=2 (fused
    compute-collective shard_map).  Acceptance is MATCHED SAMPLING: the
    verify program replays sequential decode's exact per-token key
    split/sample chain, so parity is structural, not probabilistic —
    exact equality is the bar;
  * the compile pin survives speculation: ONE verify program at fixed
    shapes ``[num_slots, spec_k+1]`` regardless of per-slot acceptance
    (trace counter checked), decode remains the named per-step fallback
    when no slot proposes;
  * constrained decoding (``submit(allowed_tokens=...)``) rides the
    SAME programs as a per-slot vocab mask: masked sampling never emits
    an out-of-set token, unconstrained siblings are untouched, and a
    slot whose draft table only predicts out-of-set tokens simply stops
    speculating (drafts truncate to empty) while the engine keeps
    serving it through decode;
  * resolution and fallback reasons are named: ``spec_k=0``, a
    too-small ``max_seq``, and the degradation ladder all surface
    through ``spec_fallback_reason``.

zz-prefixed for the same reason as test_zz_tp_serving: the tp=2 leg
drives shard_map on the 8-device CPU mesh, and the jaxlib-0.4
dispatch-race window conftest documents makes early-alphabet placement
of distributed work reproducibly fragile — sort after the window.
"""

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (NGramDraftTable, SamplingParams,
                                ServingEngine)

NEW = 16
SEEDED = SamplingParams(do_sample=True, temperature=0.9, top_k=12,
                        top_p=0.85, seed=7)


def _fresh(seed=0):
    paddle_tpu.seed(seed)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _prompts(seed=7, lengths=(5, 9, 3, 11), reps=3, vocab=256):
    """Mixed-length prompts with internal repetition, so the n-gram
    tables have structure to predict — the shared-prefix chat shape."""
    rs = np.random.RandomState(seed)
    return [(rs.randint(0, vocab, (L,)).tolist()) * reps
            for L in lengths]


def _serve(spec_k, sampling=None, prompts=None, new=NEW, **kw):
    eng = ServingEngine(_fresh(), num_slots=4, max_seq=256, min_bucket=8,
                        prefill_chunk=16, block_len=16, spec_k=spec_k,
                        **kw)
    outs = eng.serve_batch(prompts or _prompts(), max_new_tokens=new,
                           sampling=sampling, max_steps=2000)
    assert all(o.finished for o in outs)
    return [o.tokens for o in outs], eng


def _assert_spec_exercised(eng):
    """The leg proved nothing unless speculation actually ran: the ONE
    verify program traced, drafts were proposed, and some were accepted
    (the CPU-smoke acceptance bar)."""
    assert eng.core.trace_counts["verify"] == 1, eng.core.trace_counts
    snap = eng.metrics.snapshot()
    assert snap["spec_draft_tokens"] > 0
    assert eng.metrics.spec_acceptance_rate is not None


# ------------------------------------------------------------ parity

def test_greedy_parity_tp1_composed():
    base, e0 = _serve(0)
    assert e0.spec_fallback_reason is not None   # named, not silent
    toks, eng = _serve(4)
    assert eng.core.decode_path == "unfused"
    assert eng.spec_on and eng.spec_fallback_reason is None
    assert toks == base
    _assert_spec_exercised(eng)
    assert eng.metrics.spec_acceptance_rate > 0


def test_seeded_parity_tp1_composed():
    base, _ = _serve(0, sampling=SEEDED)
    toks, eng = _serve(4, sampling=SEEDED)
    assert toks == base
    _assert_spec_exercised(eng)


def test_greedy_parity_tp1_fused():
    base, e0 = _serve(0, fused_decode=True)
    assert e0.decode_path == "fused"
    toks, eng = _serve(4, fused_decode=True)
    assert eng.decode_path == "fused"
    assert toks == base
    _assert_spec_exercised(eng)
    assert eng.metrics.spec_acceptance_rate > 0


def test_seeded_parity_tp1_fused():
    base, _ = _serve(0, sampling=SEEDED, fused_decode=True)
    toks, eng = _serve(4, sampling=SEEDED, fused_decode=True)
    assert toks == base
    _assert_spec_exercised(eng)


def test_greedy_parity_tp2():
    base, e0 = _serve(0, tensor_parallel=2)
    assert e0.decode_path == "tp_fused"
    toks, eng = _serve(4, tensor_parallel=2)
    assert eng.decode_path == "tp_fused"
    assert toks == base
    _assert_spec_exercised(eng)
    assert eng.metrics.spec_acceptance_rate > 0


def test_seeded_parity_tp2():
    base, _ = _serve(0, sampling=SEEDED, tensor_parallel=2)
    toks, eng = _serve(4, sampling=SEEDED, tensor_parallel=2)
    assert toks == base
    _assert_spec_exercised(eng)


def test_spec_k_width_invariance():
    """Parity is independent of the window width: any spec_k commits
    the same sequential stream, just in differently-sized bites."""
    base, _ = _serve(0)
    for k in (1, 2, 7):
        toks, eng = _serve(k)
        assert toks == base, f"spec_k={k} diverged"
        assert eng.core.trace_counts["verify"] == 1


# ---------------------------------------------------------- resolution

def test_resolution_reasons_are_named():
    eng = ServingEngine(_fresh(), num_slots=2, max_seq=64, min_bucket=8,
                        spec_k=0)
    assert not eng.spec_on
    assert "spec_k=0" in eng.spec_fallback_reason

    # a window that cannot fit leaves speculation off with the reason
    eng = ServingEngine(_fresh(), num_slots=2, max_seq=16, min_bucket=8,
                        spec_k=16)
    assert not eng.spec_on
    assert "max_seq" in eng.spec_fallback_reason

    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(_fresh(), num_slots=2, max_seq=64, spec_k=-1)


def test_row_end_fallback_still_finishes():
    """Slots near their row end must NOT speculate (the KV window
    append would clamp into valid history) — the engine falls back to
    one token per step and still completes the request."""
    eng = ServingEngine(_fresh(), num_slots=2, max_seq=32, min_bucket=8,
                        spec_k=4)
    assert eng.spec_on
    r = eng.submit([5, 6, 7, 5, 6, 7, 5, 6], max_new_tokens=23)
    eng.run_until_complete(200)
    out = eng.result(r)
    assert out.finished and len(out.tokens) == 23
    # parity with the non-speculative engine right through the row end
    eng0 = ServingEngine(_fresh(), num_slots=2, max_seq=32, min_bucket=8)
    r0 = eng0.submit([5, 6, 7, 5, 6, 7, 5, 6], max_new_tokens=23)
    eng0.run_until_complete(200)
    assert eng0.result(r0).tokens == out.tokens


# --------------------------------------------------- constrained decode

def test_constrained_greedy_never_leaves_the_set():
    allowed = [3, 17, 42, 99, 200]
    eng = ServingEngine(_fresh(), num_slots=4, max_seq=128, min_bucket=8,
                        prefill_chunk=16, block_len=16, spec_k=3)
    h1 = eng.submit([1, 2, 3, 4, 5], max_new_tokens=12,
                    allowed_tokens=allowed)
    h2 = eng.submit([9, 9, 9, 9], max_new_tokens=12)
    eng.run_until_complete(200)
    t1 = eng.result(h1).tokens
    t2 = eng.result(h2).tokens
    assert t1 and all(t in allowed for t in t1)
    # the sibling's stream is untouched by the neighbour's mask
    ref = ServingEngine(_fresh(), num_slots=4, max_seq=128, min_bucket=8,
                        prefill_chunk=16, block_len=16, spec_k=3)
    g = ref.submit([9, 9, 9, 9], max_new_tokens=12)
    ref.run_until_complete(200)
    assert ref.result(g).tokens == t2


def test_constrained_parity_spec_on_off():
    """The mask rides INSIDE decode and verify — speculation must not
    change a constrained stream either."""
    allowed = list(range(0, 256, 5))

    def run(spec_k):
        eng = ServingEngine(_fresh(), num_slots=2, max_seq=128,
                            min_bucket=8, spec_k=spec_k)
        h = eng.submit([10, 20, 30, 10, 20, 30], max_new_tokens=16,
                       allowed_tokens=allowed)
        eng.run_until_complete(200)
        return eng.result(h).tokens, eng

    base, _ = run(0)
    toks, eng = run(4)
    assert toks == base
    assert all(t in set(allowed) for t in toks)


def test_unsatisfiable_mask_disables_slot_speculation():
    """A slot whose draft table predicts only out-of-set tokens
    proposes nothing (drafts truncate at the first disallowed token) —
    the engine serves it through plain decode, zero draft tokens."""
    # allowed set disjoint from everything the prompt's bigrams predict,
    # and from itself as a chain: {201} — after the first emit the
    # table learns 201 -> 201 which IS allowed, so pick two tokens the
    # model never chains identically... simplest: assert the FIRST
    # steps draft nothing by keeping the run to one token.
    eng = ServingEngine(_fresh(), num_slots=1, max_seq=64, min_bucket=8,
                        spec_k=4)
    assert eng.spec_on
    h = eng.submit([1, 2, 3, 4], max_new_tokens=1,
                   allowed_tokens=[250])
    eng.run_until_complete(50)
    assert eng.result(h).tokens == [250]
    # prompt bigrams (1->2, 2->3, 3->4) are all out-of-set: nothing was
    # ever proposed, speculation stayed per-slot silent
    assert eng.metrics.snapshot()["spec_draft_tokens"] == 0
    assert eng.spec_on    # engine-level speculation still armed


def test_submit_validation():
    eng = ServingEngine(_fresh(), num_slots=1, max_seq=64, min_bucket=8)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([1], allowed_tokens=[])
    with pytest.raises(ValueError, match="allowed_tokens"):
        eng.submit([1], allowed_tokens=[-1])
    with pytest.raises(ValueError, match="allowed_tokens"):
        eng.submit([1], allowed_tokens=[10 ** 9])


# ------------------------------------------------------- draft table

def test_ngram_table_proposes_and_truncates():
    t = NGramDraftTable()
    t.seed([7, 8, 7, 8, 7])
    # chained greedy walk from the (8, 7) context tail: trigram
    # (8,7)->8, then (7,8)->7, alternating for the whole window
    assert t.propose(4) == [8, 7, 8, 7]
    assert t.propose(2) == [8, 7]
    # allowed-set truncation: the chain stops at the FIRST out-of-set
    # prediction, it never skips over it
    assert t.propose(4, allowed=frozenset({8})) == [8]
    assert t.propose(4, allowed=frozenset({9999})) == []


def test_ngram_table_most_recent_wins():
    t = NGramDraftTable()
    t.seed([1, 2, 3, 9, 1, 2, 4])
    # bigram 2 -> recorded twice: the later occurrence (-> 4) wins;
    # walk from context (2, 4): 4 has no successor yet
    assert t.propose(3) == []
    t.observe(1)
    t.observe(2)
    # context (1, 2): trigram (1,2) -> 4 (most recent) over the walk
    assert t.propose(1) == [4]


def test_ngram_table_observe_extends():
    t = NGramDraftTable()
    t.seed([5, 6])
    assert t.propose(3) == []         # 6 has no successor yet
    t.observe(5)
    t.observe(6)
    # 6 -> 5 and 5 -> 6 are now known: the walk cycles from (5, 6)
    assert t.propose(4) == [5, 6, 5, 6]
    assert len(t) > 0


# ----------------------------------------------------------- metrics

def test_spec_metrics_surface():
    toks, eng = _serve(4)
    snap = eng.metrics.snapshot()
    assert snap["spec_draft_tokens"] >= snap["spec_accepted_tokens"] >= 0
    assert snap["spec_acceptance_rate"] == pytest.approx(
        snap["spec_accepted_tokens"] / snap["spec_draft_tokens"],
        abs=1e-3)
    assert eng.spec_acceptance_rate == pytest.approx(
        eng.metrics.spec_acceptance_rate)
    # window reset zeroes the spec tallies with everything else
    eng.metrics.reset()
    assert eng.metrics.snapshot()["spec_draft_tokens"] == 0
    assert eng.metrics.spec_acceptance_rate is None


def test_fleet_chaos_smoke_spec_artifacts(tmp_path):
    """Tier-1 artifact smoke (mirrors
    test_fleet_chaos_smoke_artifacts): the ``--spec`` scenario
    end-to-end through scripts/fleet_chaos_smoke.py — fleet-ledger
    conservation with speculation armed, the spec_verify burst
    ladder-disabling replica 0, and parity vs the never-speculating
    oracle fleet, all in a passing spec.json verdict."""
    import importlib.util
    import json
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "fleet_chaos_smoke",
        os.path.join(repo, "scripts", "fleet_chaos_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "artifacts")
    assert mod.main(["--out", out, "--spec", "--requests", "4"]) == 0
    with open(os.path.join(out, "spec.json")) as f:
        v = json.load(f)
    assert v["ok"] and v["all_terminal"] and v["pools_at_baseline"]
    assert v["replay_parity"]
    assert v["fired"] >= 2                       # the ladder threshold
    assert v["victim_spec_bypass"]
    assert v["victim_fallback_reason"].startswith("degraded:")
    assert v["spec_draft_tokens"] > 0
    prom = open(os.path.join(out, "metrics.prom")).read()
    assert "spec_draft_tokens" in prom or "spec" in prom
