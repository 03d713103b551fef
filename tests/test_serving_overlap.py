"""One decode program always in flight (serving/engine.py, docs/serving.md
"One program ahead").

A step dispatches its decode program BEFORE it reads the tokens of the
program the previous step dispatched.  The contract:

  * the tokens every request receives equal ``generate(seed=...)``'s and
    those of the SAME engine run through the order that reads each
    program in the step that dispatched it, greedy and sampled;
  * an ``eos`` is known one program late: that row is an overrun, its
    token reaches no ``req.tokens``, stream or journal record;
  * a program's token goes to the request it was dispatched for, never
    to one that adopted the slot in between; ``cancel()`` and a deadline
    drop it;
  * every count on one ``serving.step`` span that describes a decode
    program describes the same program;
  * a speculating engine reads before it dispatches, and says so;
  * ``step()`` stays non-zero until the last token is out, and no
    program is compiled that the synchronous order did not compile.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu.models import (DeepseekV3ForCausalLM, GPTForCausalLM,
                               JambaForCausalLM, deepseek_v3_tiny, gpt_tiny,
                               jamba_tiny)
from paddle_tpu.serving import Journal, SamplingParams, ServingEngine

SAMPLED = dict(do_sample=True, temperature=1.3, top_k=12, top_p=0.9)


@pytest.fixture(scope="module")
def gpt():
    # seeded here: the weights must not depend on which test files ran
    # before this one in the process
    with jax.default_prng_impl("rbg"):
        paddle_tpu.seed(0)
        return GPTForCausalLM(gpt_tiny())


def _prompts(seed, lengths, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (n,)) for n in lengths]


def _want(model, prompt, n, **kw):
    seq = model.generate(jnp.asarray(prompt)[None], max_new_tokens=n, **kw)
    return np.asarray(seq)[0, len(prompt):].tolist()


def _read_where_dispatched(eng):
    """Force ``eng`` through the order that reads every program in the
    step that dispatched it: the engine's own code path, taken as a
    speculating engine takes it."""
    eng.core.overlap = lambda: ("none", "forced by the test")
    return eng


def _sampling(kind, seed):
    return SamplingParams(seed=seed, **SAMPLED) if kind == "sampled" \
        else SamplingParams()


def _oracle_kw(kind, seed):
    return dict(seed=seed, **SAMPLED) if kind == "sampled" else {}


def _inner_eos(full, lo, hi):
    """An index in ``[lo, hi)`` whose token occurs nowhere before it in
    ``full``: as ``eos`` it ends the request exactly there."""
    return next(i for i in range(lo, hi) if full[i] not in full[:i])


def _drain(eng, limit=400):
    steps = 0
    while eng.step():
        steps += 1
        assert steps < limit
    return steps


def _step_spans(eng):
    return [s for s in eng.tracer.spans(lane=0) if s.name == "serving.step"]


# ------------------------------------------- (a) length-terminated parity

@pytest.mark.parametrize("kind", ["greedy", "sampled"])
def test_length_terminated_requests_match_generate_and_the_sync_order(
        gpt, kind):
    """More requests than slots, ragged lengths: the one-ahead engine's
    tokens equal ``generate``'s and the same engine's in the order that
    reads where it dispatched; no row is ever overrun."""
    prompts = _prompts(3, (5, 9, 14, 7, 11))
    new = (6, 1, 9, 2, 5)
    got = {}
    for order in ("one_ahead", "none"):
        eng = ServingEngine(gpt, num_slots=3, min_bucket=8)
        if order == "none":
            _read_where_dispatched(eng)
        assert eng.core.overlap()[0] == order
        rids = [eng.submit(p, max_new_tokens=n,
                           sampling=_sampling(kind, 20 + i))
                for i, (p, n) in enumerate(zip(prompts, new))]
        _drain(eng)
        got[order] = [eng.result(r).tokens for r in rids]
        assert all(eng.result(r).status == "finished" for r in rids)
        assert eng.metrics_dict()["overrun_tokens"] == 0
        assert eng.core.pool.free_slots == 3 and not eng.core._inflight
        ahead = [s.attrs["decode_ahead"] for s in _step_spans(eng)
                 if s.attrs["active_slots"]]
        # ahead of every dispatch but the first (nothing to be ahead of)
        assert ahead == ([0] + [1] * (len(ahead) - 1)
                         if order == "one_ahead" else [0] * len(ahead))
    want = [_want(gpt, p, n, **_oracle_kw(kind, 20 + i))
            for i, (p, n) in enumerate(zip(prompts, new))]
    assert got["one_ahead"] == want == got["none"]


# --------------------------------------------------- (b) eos: the overrun

@pytest.mark.parametrize("kind", ["greedy", "sampled"])
def test_eos_inside_the_output_is_one_overrun_row(gpt, kind, tmp_path):
    """The host learns an ``eos`` one program late: the next program has
    already run the slot once more.  That row's token is dropped: it is
    in no ``req.tokens``, stream call or journal record."""
    prompt, = _prompts(4, (9,))
    full = _want(gpt, prompt, 12, **_oracle_kw(kind, 31))
    # an eos strictly inside the output (not the first token, not one
    # the length rule would park first)
    cut = _inner_eos(full, 2, 10)
    eos = full[cut]
    journal = Journal.open(str(tmp_path / "wal"), fsync=False)
    eng = ServingEngine(gpt, num_slots=2, min_bucket=8, journal=journal)
    streamed = []
    try:
        rid = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos,
                         sampling=_sampling(kind, 31),
                         stream=lambda req, tok: streamed.append(tok))
        _drain(eng)
        out = eng.result(rid)
        assert out.tokens == full[:cut + 1] == streamed
        assert out.finish_reason == "eos" and out.status == "finished"
        assert eng.metrics_dict()["overrun_tokens"] == 1
        assert sum(s.attrs["overrun_tokens"]
                   for s in _step_spans(eng)) == 1
        journal.flush()
        assert journal.ledger()[rid]["delivered"] == cut + 1
        marks = [rec["delivered"] for rec in journal.records()
                 if "delivered" in rec]
        assert marks and all(
            n <= cut + 1 for m in marks
            for n in (m.values() if isinstance(m, dict) else [m]))
        assert eng.core.pool.free_slots == 2 and not eng.core._inflight
    finally:
        eng.close()
        journal.close()
    # the synchronous order overruns nothing
    sync = _read_where_dispatched(
        ServingEngine(gpt, num_slots=2, min_bucket=8))
    rid = sync.submit(prompt, max_new_tokens=12, eos_token_id=eos,
                      sampling=_sampling(kind, 31))
    _drain(sync)
    assert sync.result(rid).tokens == full[:cut + 1]
    assert sync.metrics_dict()["overrun_tokens"] == 0


# ------------------------------- (c) a slot adopted under a pending program

@pytest.mark.parametrize("kind", ["greedy", "sampled"])
def test_slot_released_and_adopted_while_a_program_is_pending(gpt, kind):
    """ONE slot: request A is cancelled with its token still on the
    device, B adopts the slot in the next step, before that program is
    read.  A's token must reach neither request."""
    pa, pb = _prompts(5, (6, 10))
    eng = ServingEngine(gpt, num_slots=1, min_bucket=8)
    a = eng.submit(pa, max_new_tokens=20, sampling=_sampling(kind, 41))
    b = eng.submit(pb, max_new_tokens=7, sampling=_sampling(kind, 42))
    eng.step()                      # A: first token, program 0 in flight
    eng.step()                      # program 1 in flight, program 0 read
    assert len(eng.result(a).tokens) == 2 and len(eng.core._inflight) == 1
    pending = eng.core._inflight[0]
    assert [st.req.request_id for st in pending.owners.values()] == [a]
    eng.cancel(a)
    assert eng.core.pool.free_slots == 1
    eng.step()                      # B adopts slot 0; program 1 is read
    assert eng.core._slots[0].req.request_id == b
    assert len(eng.result(a).tokens) == 2       # the dropped token
    assert eng.metrics_dict()["overrun_tokens"] == 1
    _drain(eng)
    assert eng.result(a).tokens == _want(
        gpt, pa, 20, **_oracle_kw(kind, 41))[:2]
    assert eng.result(a).status == "cancelled"
    assert eng.result(b).tokens == _want(gpt, pb, 7,
                                         **_oracle_kw(kind, 42))
    assert eng.core.pool.free_slots == 1 and not eng.core._inflight


# ----------------------------------- (d) cancel and deadline, token pending

@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_with_a_pending_token(gpt, how):
    """Both release the slot at once; the token of the program still in
    flight is dropped, the survivor's stream is untouched."""
    pa, pb = _prompts(6, (7, 12))
    eng = ServingEngine(gpt, num_slots=2, min_bucket=8)
    eng.serve_batch(_prompts(16, (7, 12)), max_new_tokens=3)   # compile
    a = eng.submit(pa, max_new_tokens=30,
                   deadline_s=2.0 if how == "deadline" else None)
    b = eng.submit(pb, max_new_tokens=9)
    eng.step()
    eng.step()
    got = len(eng.result(a).tokens)
    assert got == 2 and len(eng.core._inflight) == 1
    if how == "cancel":
        eng.cancel(a)
    else:
        time.sleep(2.1)             # the next step's sweep expires it
    eng.step()
    out = eng.result(a)
    assert out.status == ("cancelled" if how == "cancel"
                          else "deadline_exceeded")
    assert out.tokens == _want(gpt, pa, 30)[:len(out.tokens)]
    if how == "cancel":
        assert len(out.tokens) == got
    assert eng.metrics_dict()["overrun_tokens"] == 1
    _drain(eng)
    assert eng.result(b).tokens == _want(gpt, pb, 9)
    assert eng.core.pool.free_slots == 2 and not eng.core._inflight


# ---------------------------------------------- (e) one row from max_seq

@pytest.mark.parametrize("ending", ["length", "eos"])
def test_request_one_row_from_max_seq(gpt, ending):
    """``prompt_len + max_new_tokens == max_seq``: the length rule parks
    the slot before a program could run past its row, and an ``eos``
    overrun still writes inside it."""
    max_seq = 32
    prompt, = _prompts(7, (max_seq - 8,))
    full = _want(gpt, prompt, 8)
    eos = None
    want = full
    if ending == "eos":
        cut = _inner_eos(full, 1, 7)
        eos, want = full[cut], full[:cut + 1]
    eng = ServingEngine(gpt, num_slots=2, min_bucket=8, max_seq=max_seq)
    rid = eng.submit(prompt, max_new_tokens=8, eos_token_id=eos)
    rows = []
    while eng.step():
        rows.append(int(np.asarray(eng.core.pool.seq_pos).max()))
    assert eng.result(rid).tokens == want
    # the device never held more rows than the row has
    assert max(rows) <= max_seq - 1
    assert eng.metrics_dict()["overrun_tokens"] == (ending == "eos")
    with pytest.raises(ValueError, match="exceeds the pool max_seq"):
        eng.submit(prompt, max_new_tokens=9)


# ------------------------------------ (f) stateful and routed fixtures

def _stateful():
    paddle_tpu.seed(0)
    model = JambaForCausalLM(jamba_tiny())
    model.eval()
    return model, dict(num_slots=3, min_bucket=8, max_seq=96,
                       prefill_chunk=16, enable_prefix_cache=False)


def _routed():
    paddle_tpu.seed(0)
    model = DeepseekV3ForCausalLM(deepseek_v3_tiny(initializer_range=0.15))
    model.eval()
    return model, dict(num_slots=4, min_bucket=8, max_seq=96,
                       prefill_chunk=16, enable_prefix_cache=False)


@pytest.mark.parametrize("family", ["stateful", "routed"])
def test_stateful_and_routed_models_run_one_ahead(family):
    """A Jamba-shaped model (its recurrent state donated program to
    program) and a DeepSeek-shaped one (its expert counts ride the token
    readback): tokens equal ``generate``'s and the synchronous order's,
    and every span's counts describe ONE program."""
    model, kw = {"stateful": _stateful, "routed": _routed}[family]()
    prompts = _prompts(8, (21, 6, 37, 11, 18), vocab=model.cfg.vocab_size)
    new = (5, 7, 3, 6, 4)
    want = [_want(model, p, n) for p, n in zip(prompts, new)]
    for order in ("one_ahead", "none"):
        eng = ServingEngine(model, **kw)
        if order == "none":
            _read_where_dispatched(eng)
        try:
            rids = [eng.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, new)]
            _drain(eng)
            assert [eng.result(r).tokens for r in rids] == want, order
            assert eng.core.overlap()[0] == order
            spans = _step_spans(eng)
            decoding = [s for s in spans if s.attrs["active_slots"]]
            assert decoding
            if family == "stateful":
                assert all(s.attrs["state_slots"] == kw["num_slots"]
                           for s in decoding)
                continue
            layers, experts = model.expert_routing_spec()
            top_k = model.cfg.num_experts_per_tok
            for s in spans:
                # the bound benchmarks/lib/moe_flops_bytes.experts_cap
                # holds a traced run to: a span that mixed two
                # programs' counts would break it where slots leave
                cap = min(layers * experts,
                          layers * top_k * s.attrs["active_slots"])
                assert s.attrs["experts_touched"] <= cap, (order, s.attrs)
                assert s.attrs["expert_rows_max"] \
                    <= s.attrs["active_slots"]
            # every program that ran live rows counted its experts
            assert all(s.attrs["experts_touched"] >= top_k
                       for s in decoding)
        finally:
            eng.close()


# -------------------------------------------------- (g) speculation, drain

def test_speculating_engine_reads_before_it_dispatches(gpt):
    """The drafts of the next dispatch come from this step's tokens: a
    speculating engine keeps the synchronous order and says why; its
    tokens are those of the plain engine; bypassed, it runs ahead."""
    prompt = np.tile([5, 6, 7, 8], 6)
    eng = ServingEngine(gpt, spec_k=3, num_slots=2, min_bucket=8)
    assert eng.core.spec_on
    assert eng.core.overlap() == ("none", "speculation")
    rid = eng.submit(prompt, max_new_tokens=12)
    _drain(eng)
    assert eng.result(rid).tokens == _want(gpt, prompt, 12)
    assert eng.metrics_dict()["spec_accepted_tokens"] > 0
    assert all(s.attrs["decode_ahead"] == 0 for s in _step_spans(eng))
    events = eng.tracer.events("decode_block")
    assert all(e[3]["overlap"] == "none"
               and e[3]["overlap_reason"] == "speculation"
               for e in events)
    eng.core.spec_bypass = True
    assert eng.core.overlap() == ("one_ahead", None)
    plain = ServingEngine(gpt, num_slots=2, min_bucket=8)
    plain.serve_batch([prompt], max_new_tokens=3)
    event, = plain.tracer.events("decode_block")
    assert event[3]["overlap"] == "one_ahead"
    assert event[3]["overlap_reason"] == ""


def test_step_is_nonzero_until_the_last_token_is_out(gpt):
    """``while eng.step()`` and ``run_until_complete`` drain the program
    in flight: the request stays counted until its last token has been
    emitted, and an overrun program is still read."""
    prompt, = _prompts(9, (8,))
    eng = ServingEngine(gpt, num_slots=2, min_bucket=8)
    rid = eng.submit(prompt, max_new_tokens=3)
    left, tokens = [], []
    for _ in range(10):
        left.append(eng.step())
        tokens.append(len(eng.result(rid).tokens))
        if not left[-1]:
            break
    # first token; nothing read yet; second; third (slot parked ahead)
    assert tokens == [1, 2, 3] and left == [1, 1, 0]
    assert not eng.core.has_work()
    # an eos leaves an overrun program behind: has_work until it is read
    prompt, = _prompts(4, (9,))
    full = _want(gpt, prompt, 12)
    cut = _inner_eos(full, 2, 10)
    rid = eng.submit(prompt, max_new_tokens=12, eos_token_id=full[cut])
    while not eng.result(rid).finished:
        assert eng.step()
    assert eng.core._inflight and eng.core.has_work()
    assert eng.core.scheduler.active == 0
    assert eng.run_until_complete(10) == 1
    assert not eng.core._inflight and not eng.core.has_work()
    assert eng.core.stall_snapshot()["programs_in_flight"] == 0


def test_no_new_program(gpt):
    """The engine compiles what the synchronous order compiles (parking
    a slot ahead of its release is the scatter ``free`` already ran, the
    early transfer is no program): the same trace counts, and jax's own
    count of backend compile requests over a warm window with slots
    parked ahead, an overrun and a slot adopted again is zero."""
    from benchmarks.lib.compile_clock import CompileClock
    prompts = _prompts(10, (5, 12, 20))
    eos = _want(gpt, prompts[1], 6)[2]
    counts = {}
    for order in ("one_ahead", "none"):
        # (no prefix cache: the second run's hits would compile its
        # gather, in either order)
        eng = ServingEngine(gpt, num_slots=2, min_bucket=8,
                            enable_prefix_cache=False)
        if order == "none":
            _read_where_dispatched(eng)

        def run():
            rids = [eng.submit(p, max_new_tokens=6, eos_token_id=eos)
                    for p in prompts]
            _drain(eng)
            return [eng.result(r).tokens for r in rids]

        first = run()
        with CompileClock() as clock:
            assert run() == first
        assert clock.programs == 0, order
        counts[order] = (dict(eng.core.trace_counts), first)
    assert counts["one_ahead"] == counts["none"]
    assert counts["one_ahead"][0]["decode"] == 1
