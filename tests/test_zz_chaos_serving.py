"""Chaos suite for the serving fault-tolerance layer (ISSUE 8).

THE invariant, driven through every injection point in
``serving/faults.py``: after any injected fault sequence,

  (a) every submitted request reaches a TERMINAL status with a reason
      (finished | cancelled | deadline_exceeded | rejected | failed) —
      nothing is ever silently lost;
  (b) ``KVPool``/``BlockPool`` free counts and radix-cache refcounts
      return to their pre-fault baseline — faults never leak capacity;
  (c) with faults off the engine stays token-for-token identical to
      ``model.generate`` (the in-program finiteness probe is a no-op on
      finite logits; the existing parity tests in test_serving.py are
      untouched and re-pinned here through a faults-attached engine);
  (d) the compile-count pin survives a quarantine rebuild — the program
      set stays {chunk} + buckets + ONE decode per device plane.

zz-prefixed for the same reason as test_zz_bench_projection /
test_zz_decode_block: early-alphabet placement reproducibly re-triggers
the jaxlib-0.4 CPU dispatch-race segfault around the distributed test
window (see tests/conftest.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (EngineStalledError, FaultError,
                                FaultInjector, FaultToleranceConfig,
                                RequestRejected, SamplingParams,
                                ServingEngine, bucket_length,
                                finite_or_sentinel)

TERMINAL = {"finished", "cancelled", "deadline_exceeded", "rejected",
            "failed"}


@pytest.fixture(scope="module")
def gpt():
    with jax.default_prng_impl("rbg"):
        return GPTForCausalLM(gpt_tiny())


def _prompts(seed, lengths, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (L,)) for L in lengths]


def _want(model, prompt, n=5):
    seq = model.generate(jnp.asarray(prompt)[None], max_new_tokens=n)
    return np.asarray(seq)[0, len(prompt):]


def make_engine(gpt, retries=3, ladder=2, circuit=3, window=512,
                **kw):
    """Fault-tolerant engine with an attached injector and zero backoff
    sleeps (the chaos suite drives logic, not wall clocks)."""
    faults = FaultInjector()
    ft = FaultToleranceConfig(max_step_retries=retries,
                              backoff_base_s=0.0,
                              ladder_threshold=ladder,
                              circuit_quarantine_limit=circuit,
                              circuit_window_steps=window)
    eng = ServingEngine(gpt, num_slots=kw.pop("num_slots", 3),
                        min_bucket=kw.pop("min_bucket", 8),
                        fault_tolerance=ft, faults=faults, **kw)
    return eng, faults


def assert_accounting(eng, rids):
    """Invariants (a) + (b) after a drain."""
    core = eng.core
    for rid in rids:
        out = eng.result(rid)
        assert out.finished, f"request {rid} not terminal"
        assert out.status in TERMINAL, (rid, out.status)
        assert out.status_reason, (rid, out.status)
    assert core.scheduler.active == 0
    assert core.scheduler.queue_depth == 0
    assert not core._prefills
    assert core.pool.free_slots == core.num_slots
    if core.prefix_cache is not None:
        bp = core.block_pool
        assert bp.free_blocks + bp.used_blocks == bp.num_blocks
        nodes = 0
        stack = list(core.prefix_cache.root.children.values())
        while stack:
            n = stack.pop()
            assert n.refcount == 0, "leaked radix pin"
            nodes += 1
            stack.extend(n.children.values())
        assert nodes == bp.used_blocks   # tree<->pool ownership intact


# ----------------------------------------------------------- pure units

def test_fault_injector_arming_semantics():
    fi = FaultInjector()
    with pytest.raises(ValueError, match="unknown fault site"):
        fi.enable("bogus")
    fi.enable("step", at=1, times=2)
    assert fi.check("step") is None          # hit 0: before window
    assert fi.check("step") is not None      # hit 1
    assert fi.check("step") is not None      # hit 2
    assert fi.check("step") is None          # hit 3: window spent
    assert fi.fired["step"] == 2 and fi.hits["step"] == 4
    fi.enable("kv_alloc")
    with pytest.raises(FaultError, match="kv_alloc") as ei:
        fi.fire("kv_alloc")
    assert ei.value.site == "kv_alloc"
    fi.disable("kv_alloc")
    assert fi.fire("kv_alloc") is False      # disarmed: no raise
    fi.disable("step")
    assert not fi.active


def test_finite_or_sentinel_unit():
    logits = jnp.asarray([[1.0, 2.0], [jnp.nan, 0.5], [jnp.inf, 1.0]])
    toks = jnp.asarray([5, 7, 9], jnp.int32)
    out = np.asarray(finite_or_sentinel(logits, toks))
    np.testing.assert_array_equal(out, [5, -1, -1])


def test_health_circuit_breaker_window():
    from paddle_tpu.serving.health import EngineHealth
    h = EngineHealth(FaultToleranceConfig(circuit_quarantine_limit=2,
                                          circuit_window_steps=10))
    assert h.state == "healthy"
    assert h.record_step_fault("x") is not None       # retry 1
    assert h.state == "degraded"
    q = h.enter_quarantine("x")
    assert h.state == "quarantined" and not h.circuit_open
    h.leave_quarantine(q)
    for _ in range(20):
        h.on_step_ok()                                # outrun the window
    q = h.enter_quarantine("y")
    h.leave_quarantine(q)
    assert not h.circuit_open   # 2 quarantines but 20 steps apart
    q = h.enter_quarantine("z")                       # 2 within window
    h.leave_quarantine(q)
    assert h.circuit_open and h.state == "circuit_open"


# ------------------------------------------- injected faults, recovered

def test_kv_alloc_fault_retried_to_parity(gpt):
    eng, faults = make_engine(gpt)
    prompts = _prompts(0, (3, 7, 5, 9))
    faults.enable("kv_alloc")
    try:
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_complete(300)
    finally:
        faults.disable("kv_alloc")
    assert faults.fired["kv_alloc"] == 1
    m = eng.metrics_dict()
    assert m["faults"] >= 1 and m["step_retries"] >= 1
    assert m["quarantines"] == 0
    for rid, p in zip(rids, prompts):
        out = eng.result(rid)
        assert out.status == "finished"
        np.testing.assert_array_equal(out.tokens, _want(gpt, p))
    assert_accounting(eng, rids)
    assert eng.health.state == "healthy"


def test_gather_fault_ladder_bypasses_prefix_cache(gpt):
    eng, faults = make_engine(gpt, block_len=8, num_slots=2)
    prefix = _prompts(1, (32,))[0]
    warm = np.concatenate([prefix, _prompts(2, (4,))[0]])
    r0 = eng.submit(warm, max_new_tokens=3)
    eng.run_until_complete(200)          # populate the radix tree
    hits = [np.concatenate([prefix, s]) for s in _prompts(3, (4, 4))]
    faults.enable("gather", times=2)     # ladder_threshold faults
    try:
        rids = [eng.submit(p, max_new_tokens=3) for p in hits]
        eng.run_until_complete(200)
    finally:
        faults.disable("gather")
    assert faults.fired["gather"] == 2
    assert "prefix_cache" in eng.degraded_subsystems
    assert eng.health.state == "degraded"
    m = eng.metrics_dict()
    assert m["degradation_level"] == 1
    for rid, p in zip(rids, hits):
        out = eng.result(rid)
        assert out.status == "finished"
        assert out.prefix_hit_tokens == 0      # served as contained miss
        np.testing.assert_array_equal(out.tokens, _want(gpt, p, 3))
    # bypassed: a fresh cache-hit prompt no longer even matches
    r3 = eng.submit(np.concatenate([prefix, _prompts(4, (4,))[0]]),
                    max_new_tokens=3)
    eng.run_until_complete(200)
    assert eng.result(r3).prefix_hit_tokens == 0
    assert_accounting(eng, [r0] + rids + [r3])


def test_scatter_and_block_faults_contained(gpt):
    # ladder=3: the scatter + block_alloc faults must NOT bypass the
    # cache before the third submit reaches the block_exhausted point
    eng, faults = make_engine(gpt, ladder=3, block_len=8, num_slots=2)
    prompts = _prompts(5, (17, 19, 21))
    faults.enable("scatter")             # first insert raises
    try:
        a = eng.submit(prompts[0], max_new_tokens=3)
        eng.run_until_complete(200)
    finally:
        faults.disable("scatter")
    faults.enable("block_alloc")         # next insert's alloc raises
    try:
        b = eng.submit(prompts[1], max_new_tokens=3)
        eng.run_until_complete(200)
    finally:
        faults.disable("block_alloc")
    faults.enable("block_exhausted", times=8)   # graceful partial insert
    try:
        c = eng.submit(prompts[2], max_new_tokens=3)
        eng.run_until_complete(200)
    finally:
        faults.disable("block_exhausted")
    assert faults.fired["scatter"] == 1
    assert faults.fired["block_alloc"] == 1
    assert faults.fired["block_exhausted"] >= 1
    for rid, p in zip((a, b, c), prompts):
        out = eng.result(rid)
        assert out.status == "finished"
        np.testing.assert_array_equal(out.tokens, _want(gpt, p, 3))
    assert_accounting(eng, [a, b, c])
    # scatter + block_alloc counted 2 ladder faults; graceful pool
    # exhaustion is a partial insert, NOT a fault — below threshold 3
    # the cache stays active
    assert "prefix_cache" not in eng.degraded_subsystems
    assert eng.metrics_dict()["faults"] == 2


def test_step_fault_single_retry_keeps_parity(gpt):
    eng, faults = make_engine(gpt)
    prompts = _prompts(6, (3, 8, 5))
    faults.enable("step")                # one decode-region raise
    try:
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_complete(300)
    finally:
        faults.disable("step")
    assert faults.fired["step"] == 1
    m = eng.metrics_dict()
    assert m["step_retries"] == 1 and m["quarantines"] == 0
    for rid, p in zip(rids, prompts):
        out = eng.result(rid)
        assert out.status == "finished"
        np.testing.assert_array_equal(out.tokens, _want(gpt, p))
    assert_accounting(eng, rids)
    assert eng.health.state == "healthy"


def test_step_fault_quarantine_fails_inflight_recovers_queued(gpt):
    """Retry budget spent -> quarantine: in-flight requests end terminal
    `failed` (not lost), queued work re-serves to parity on the rebuilt
    device plane, and the compile pin (d) holds: exactly ONE decode
    program per device plane."""
    eng, faults = make_engine(gpt, retries=2, num_slots=2,
                              enable_prefix_cache=False)
    prompts = _prompts(7, (3, 6, 5, 9, 7))
    buckets = {bucket_length(len(p), 8, 128) for p in prompts}
    # at=2: the first plane DECODES (its program traces) before the 3
    # consecutive faults (2 retries + 1) force the quarantine rebuild —
    # the compile pin below needs both planes to have dispatched
    faults.enable("step", at=2, times=3)
    try:
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_complete(400)
    finally:
        faults.disable("step")
    m = eng.metrics_dict()
    assert m["quarantines"] == 1
    outs = [eng.result(r) for r in rids]
    failed = [o for o in outs if o.status == "failed"]
    done = [o for o in outs if o.status == "finished"]
    assert len(failed) == 2              # the two in-flight slots
    assert all("quarantine" in o.status_reason for o in failed)
    assert len(done) == 3                # queued work survived
    for o, p in zip(outs, prompts):
        if o.status == "finished":
            np.testing.assert_array_equal(o.tokens, _want(gpt, p, 4))
    assert_accounting(eng, rids)
    assert eng.health.state == "healthy"
    # (d) ONE decode program per device plane, buckets re-trace at most
    # once each on the rebuilt plane
    assert eng.core.trace_counts["decode"] == 2
    assert eng.core.trace_counts["prefill"] <= 2 * len(buckets)


def test_tp_quarantine_rebuilds_sharded_plane():
    """TP chaos (ISSUE 9): the quarantine recovery path on a
    tensor-parallel mesh.  A spent retry budget rebuilds the device
    plane SHARDED — slabs back on the kv-head axis, pools and radix
    refcounts at baseline (the total-accounting invariant holds under a
    mesh), queued work re-serves to token parity with a clean tp=1
    engine, and the compile pin stays ONE decode per plane."""
    import paddle_tpu
    paddle_tpu.seed(11)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    paddle_tpu.seed(11)
    oracle = GPTForCausalLM(gpt_tiny())
    oracle.eval()
    eng, faults = make_engine(model, retries=2, num_slots=2,
                              tensor_parallel=2)
    prompts = _prompts(7, (3, 6, 5, 9, 7))
    faults.enable("step", at=2, times=3)   # first plane decodes, then
    try:                                   # 3 faults force quarantine
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_complete(400)
    finally:
        faults.disable("step")
    assert eng.metrics_dict()["quarantines"] == 1
    outs = [eng.result(r) for r in rids]
    assert sum(o.status == "failed" for o in outs) == 2   # in-flight
    assert sum(o.status == "finished" for o in outs) == 3  # queued
    for o, p in zip(outs, prompts):
        if o.status == "finished":
            np.testing.assert_array_equal(o.tokens, _want(oracle, p, 4))
    assert_accounting(eng, rids)
    assert eng.health.state == "healthy"
    core = eng.core
    # the REBUILT plane is still tensor-parallel: slabs sharded on the
    # kv-head axis over the serving mesh, block slab included
    # (a spec may drop trailing unsharded dims; compare the sharded ones)
    assert tuple(core.pool.ks[0].sharding.spec)[:3] == \
        (None, None, "mp")
    assert tuple(core.block_pool.bks[0].sharding.spec)[:3] == \
        (None, None, "mp")
    assert core.trace_counts["decode"] == 2   # ONE per device plane
    assert eng.decode_path == "tp_fused"


def test_tp_fused_block_quarantine_rebuild():
    """TP chaos on the SHARDED Pallas decode block (ISSUE 12): a spent
    retry budget on a ``tp_fused_block`` engine quarantines, the
    rebuilt plane still decodes through the sharded Pallas block
    (degradation is for fused-path faults — a core step fault must not
    silently demote the path), slabs come back sharded on the kv-head
    axis, the total-accounting invariant holds, queued work re-serves
    to parity with a clean tp=1 engine, and the compile pin stays ONE
    decode per plane."""
    import paddle_tpu
    paddle_tpu.seed(13)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    paddle_tpu.seed(13)
    oracle = GPTForCausalLM(gpt_tiny())
    oracle.eval()
    eng, faults = make_engine(model, retries=2, num_slots=2,
                              tensor_parallel=2, fused_decode=True)
    assert eng.decode_path == "tp_fused_block"
    prompts = _prompts(12, (3, 6, 5, 9, 7))
    # a fault in the DECODE phase of a fused-path engine is ladder
    # territory by design (composed fallback exists), so quarantine
    # must come from a CORE phase: fail eviction — it runs after the
    # step's fault-phase window closes — three times, spending the
    # retry budget
    real_evict = eng.core._evict_finished
    state = {"calls": 0}

    def flaky_evict():
        state["calls"] += 1
        if 2 <= state["calls"] <= 4:
            raise RuntimeError("injected core fault (eviction)")
        return real_evict()

    eng.core._evict_finished = flaky_evict
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_complete(400)
    assert eng.metrics_dict()["quarantines"] == 1
    outs = [eng.result(r) for r in rids]
    # in-flight work that already emitted everything settles finished
    # (PR 8 semantics); anything mid-stream fails terminally; queued
    # work re-serves — and every finished transcript matches the oracle
    assert all(o.status in TERMINAL for o in outs)
    assert sum(o.status == "finished" for o in outs) >= 3
    for o, p in zip(outs, prompts):
        if o.status == "finished":
            np.testing.assert_array_equal(o.tokens, _want(oracle, p, 4))
    assert_accounting(eng, rids)
    assert eng.health.state == "healthy"
    core = eng.core
    assert eng.decode_path == "tp_fused_block"
    assert tuple(core.pool.ks[0].sharding.spec)[:3] == \
        (None, None, "mp")
    assert core.trace_counts["decode"] == 2   # ONE per device plane


def test_tp_fused_block_ladder_degrades_to_composed():
    """A fault attributed to the SHARDED fused decode path feeds the
    degradation ladder, and the rung lands on the composed
    compute-collective program (``tp_fused``) — the same order as the
    resolve chain — not all the way down to the GSPMD decode; the
    engine keeps serving through it."""
    import paddle_tpu
    paddle_tpu.seed(14)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    eng, faults = make_engine(model, retries=3, ladder=1, num_slots=2,
                              tensor_parallel=2, fused_decode=True)
    assert eng.decode_path == "tp_fused_block"
    prompts = _prompts(15, (3, 6, 4))
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.step()                         # admit + first prefills
    # fail the decode dispatch itself once: the watchdog attributes it
    # to the fused path (ladder threshold 1 -> immediate demotion)
    real_dispatch = eng.core._decode_dispatch
    calls = {"n": 0}

    def flaky_dispatch():
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("injected fused dispatch fault")
        return real_dispatch()

    eng.core._decode_dispatch = flaky_dispatch
    eng.run_until_complete(400)
    assert eng.decode_path == "tp_fused"
    assert eng.decode_fallback_reason.startswith("degraded:")
    outs = [eng.result(r) for r in rids]
    assert all(o.status == "finished" for o in outs)
    assert_accounting(eng, rids)


def test_persistent_fault_opens_circuit(gpt):
    eng, faults = make_engine(gpt, retries=1, circuit=2, num_slots=2)
    prompts = _prompts(8, (3, 5, 7, 4))
    faults.enable("step", times=50)      # never recovers
    try:
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_complete(400)
    finally:
        faults.disable("step")
    m = eng.metrics_dict()
    assert m["quarantines"] == 2
    assert eng.health.state == "circuit_open"
    outs = [eng.result(r) for r in rids]
    assert all(o.status == "failed" for o in outs)
    assert_accounting(eng, rids)
    # fail-fast surface: submits reject, stepping is a no-op
    with pytest.raises(RequestRejected, match="circuit_open") as ei:
        eng.submit(prompts[0], max_new_tokens=2)
    assert ei.value.output.status == "rejected"
    assert eng.step() == 0
    assert m["requests_failed"] == len(prompts)


def test_nan_logits_fails_only_implicated_request(gpt):
    eng, faults = make_engine(gpt)
    prompts = _prompts(9, (4, 6, 8))
    faults.enable("nan_logits")          # poisons the lowest live slot
    try:
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_complete(300)
    finally:
        faults.disable("nan_logits")
    assert faults.fired["nan_logits"] == 1
    outs = [eng.result(r) for r in rids]
    assert outs[0].status == "failed"
    assert "non-finite" in outs[0].status_reason
    for o, p in zip(outs[1:], prompts[1:]):
        assert o.status == "finished"
        np.testing.assert_array_equal(o.tokens, _want(gpt, p))
    m = eng.metrics_dict()
    assert m["requests_failed"] == 1 and m["quarantines"] == 0
    assert_accounting(eng, rids)
    # the poisoned slot row is overwritten wholesale by the next adopt:
    # a fresh request through the same engine is token-exact again
    p = _prompts(10, (5,))[0]
    r = eng.submit(p, max_new_tokens=5)
    eng.run_until_complete(200)
    np.testing.assert_array_equal(eng.result(r).tokens, _want(gpt, p))


def test_slow_step_fault_counts_and_finishes(gpt):
    eng, faults = make_engine(gpt, num_slots=2)
    faults.enable("slow_step", seconds=0.01)
    try:
        rids = [eng.submit(p, max_new_tokens=3)
                for p in _prompts(11, (3, 5))]
        eng.run_until_complete(200)
    finally:
        faults.disable("slow_step")
    assert faults.fired["slow_step"] == 1
    assert eng.metrics_dict()["faults"] >= 1
    assert all(eng.result(r).status == "finished" for r in rids)
    assert_accounting(eng, rids)


# --------------------------------------- deadlines / cancel / rejection

def test_ttft_deadline_expires_queued_request(gpt):
    eng, _ = make_engine(gpt, num_slots=2)
    normal = _prompts(12, (4, 6))
    rids = [eng.submit(p, max_new_tokens=4) for p in normal]
    doomed = eng.submit(_prompts(13, (5,))[0], max_new_tokens=4,
                        ttft_deadline_s=0.0)
    eng.run_until_complete(200)
    out = eng.result(doomed)
    assert out.status == "deadline_exceeded"
    assert "TTFT deadline" in out.status_reason
    assert out.tokens == []              # never admitted, never decoded
    for rid, p in zip(rids, normal):
        np.testing.assert_array_equal(eng.result(rid).tokens,
                                      _want(gpt, p, 4))
    assert_accounting(eng, rids + [doomed])
    assert eng.metrics_dict()["requests_deadline_exceeded"] == 1


def test_e2e_deadline_unwinds_mid_decode(gpt):
    eng, _ = make_engine(gpt, num_slots=2)
    keep = eng.submit(_prompts(14, (4,))[0], max_new_tokens=6)
    rid = eng.submit(_prompts(15, (6,))[0], max_new_tokens=64,
                     deadline_s=60.0)
    for _ in range(3):
        eng.step()                       # admitted + a few tokens
    req = eng._requests[rid]
    assert req.tokens and not req.finished
    req.deadline_s = 1e-4                # deterministic expiry
    eng.step()
    out = eng.result(rid)
    assert out.status == "deadline_exceeded"
    assert "end-to-end deadline" in out.status_reason
    assert len(out.tokens) >= 1          # partial output survives
    eng.run_until_complete(200)
    assert eng.result(keep).status == "finished"
    assert_accounting(eng, [keep, rid])


def test_purge_mid_chunked_prefill_releases_everything(gpt):
    """Satellite: purge() during chunked prefill releases the slot, the
    staging rows and the pinned radix path (pool counters + refcounts),
    and an identical re-submit re-admits cleanly."""
    eng, _ = make_engine(gpt, num_slots=2, block_len=8,
                         prefill_chunk=8)
    core = eng.core
    prefix = _prompts(16, (40,))[0]
    warm = np.concatenate([prefix, _prompts(17, (6,))[0]])
    w = eng.submit(warm, max_new_tokens=2)
    eng.run_until_complete(300)
    eng.purge(w)
    free_slots = core.pool.free_slots
    free_blocks = core.block_pool.free_blocks
    victim = np.concatenate([prefix, _prompts(18, (30,))[0]])
    rid = eng.submit(victim, max_new_tokens=4)
    eng.step()                           # admit + first chunk only
    assert core._prefills and not core._prefills[0].done
    st = core._prefills[0]
    assert st.match is not None and st.match.tokens > 0
    assert any(n.refcount > 0 for n in st.match._nodes)
    assert core.pool.free_slots == free_slots - 1
    out = eng.purge(rid)                 # purge MID-flight -> cancel
    assert out.status == "cancelled"
    assert "purged" in out.status_reason
    assert not core._prefills
    assert core.pool.free_slots == free_slots
    assert core.block_pool.free_blocks == free_blocks
    assert all(n.refcount == 0 for n in st.match._nodes)
    # identical re-submit re-admits and completes cleanly
    rid2 = eng.submit(victim, max_new_tokens=4)
    eng.run_until_complete(300)
    out2 = eng.result(rid2)
    assert out2.status == "finished"
    np.testing.assert_array_equal(out2.tokens, _want(gpt, victim, 4))
    assert_accounting(eng, [rid2])


def test_cancel_each_state(gpt):
    eng, _ = make_engine(gpt, num_slots=2)
    prompts = _prompts(19, (4, 5, 6))
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()                           # 2 decoding, 1 queued
    queued = eng.cancel(rids[2])
    assert queued.status == "cancelled" and queued.tokens == []
    decoding = eng.cancel(rids[0])
    assert decoding.status == "cancelled"
    assert eng.core.pool.free_slots == 1
    eng.run_until_complete(200)
    out = eng.result(rids[1])
    assert out.status == "finished"
    np.testing.assert_array_equal(out.tokens, _want(gpt, prompts[1], 8))
    # cancellation is idempotent and stream() terminates on it
    again = eng.cancel(rids[0])
    assert again.status == "cancelled"
    assert_accounting(eng, rids)


def test_bounded_queue_rejects_with_retry_hint(gpt):
    eng, _ = make_engine(gpt, num_slots=1, max_queue=2)
    prompts = _prompts(20, (3, 4, 5, 6))
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts[:2]]
    with pytest.raises(RequestRejected, match="queue_full") as ei:
        eng.submit(prompts[2], max_new_tokens=3)
    assert ei.value.retry_after_s is None        # no throughput history
    assert ei.value.output.status == "rejected"
    assert ei.value.output.status_reason == "queue_full"
    eng.run_until_complete(200)
    rids += [eng.submit(p, max_new_tokens=3) for p in prompts[:2]]
    with pytest.raises(RequestRejected, match="queue_full") as ei:
        eng.submit(prompts[3], max_new_tokens=3)
    assert ei.value.retry_after_s is not None    # live-metrics hint
    assert ei.value.retry_after_s > 0
    eng.run_until_complete(200)
    assert_accounting(eng, rids)
    assert eng.metrics_dict()["requests_rejected"] == 2


def test_slo_admission_rejects_unattainable_ttft(gpt):
    eng, _ = make_engine(gpt, num_slots=2)
    rids = [eng.submit(p, max_new_tokens=4)
            for p in _prompts(21, (4, 7))]
    eng.run_until_complete(200)          # build throughput history
    with pytest.raises(RequestRejected, match="slo_unattainable"):
        eng.submit(_prompts(22, (5,))[0], max_new_tokens=4,
                   ttft_deadline_s=1e-9)
    # an attainable deadline still admits
    r = eng.submit(_prompts(22, (5,))[0], max_new_tokens=4,
                   ttft_deadline_s=60.0)
    eng.run_until_complete(200)
    assert eng.result(r).status == "finished"
    assert_accounting(eng, rids + [r])


def test_submit_validation_is_loud_and_early(gpt):
    eng, _ = make_engine(gpt)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(np.zeros(120, np.int32), max_new_tokens=20)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2, 3], max_new_tokens=0)
    with pytest.raises(ValueError, match="ttft_deadline_s"):
        eng.submit([1, 2, 3], max_new_tokens=2, ttft_deadline_s=-1.0)
    assert eng.metrics_dict()["requests_submitted"] == 0


# ------------------------------------------------- stall / parity / obs

def test_stall_detector_raises_with_snapshot(gpt):
    eng, _ = make_engine(gpt)
    eng.submit(_prompts(23, (4,))[0], max_new_tokens=2)
    orig = eng.core.scheduler.admit
    eng.core.scheduler.admit = lambda *a, **k: []   # wedge admission
    try:
        with pytest.raises(EngineStalledError, match="no progress") as ei:
            eng.run_until_complete(stall_steps=5)
    finally:
        eng.core.scheduler.admit = orig
    snap = ei.value.snapshot
    assert snap["queue_depth"] == 1
    assert snap["free_slots"] == eng.core.num_slots
    assert len(snap["seq_pos"]) == eng.core.num_slots
    assert snap["health"] in ("healthy", "degraded")
    eng.run_until_complete(100)          # un-wedged: drains fine


def test_faults_attached_but_unarmed_keeps_exact_parity(gpt):
    """(c) zero-overhead-when-off: an armed-capable engine with nothing
    armed is token-for-token generate(), greedy AND seeded sampling."""
    eng, faults = make_engine(gpt)
    assert not faults.active
    prompts = _prompts(24, (3, 9, 6))
    sp = SamplingParams(do_sample=True, temperature=1.3, top_k=7,
                        top_p=0.9, seed=5)
    g = [eng.submit(p, max_new_tokens=5) for p in prompts[:2]]
    s = eng.submit(prompts[2], max_new_tokens=5, sampling=sp)
    eng.run_until_complete(200)
    for rid, p in zip(g, prompts[:2]):
        np.testing.assert_array_equal(eng.result(rid).tokens,
                                      _want(gpt, p))
    want = np.asarray(gpt.generate(
        jnp.asarray(prompts[2])[None], max_new_tokens=5, do_sample=True,
        temperature=1.3, top_k=7, top_p=0.9, seed=5))[0, len(prompts[2]):]
    np.testing.assert_array_equal(eng.result(s).tokens, want)
    assert_accounting(eng, g + [s])
    assert eng.health.state == "healthy"
    assert eng.metrics_dict()["faults"] == 0


def test_stream_callback_fault_contained_to_request(gpt):
    """A raising CLIENT stream callback fails exactly its own request;
    the other slots' tokens from the same step's readback are never
    dropped (a mid-harvest raise that reached the watchdog would skip
    one token per surviving slot on retry — parity-destroying)."""
    eng, _ = make_engine(gpt)

    def bad_stream(req, tok):
        raise RuntimeError("client sink broke")

    prompts = _prompts(26, (4, 6, 8))
    a = eng.submit(prompts[0], max_new_tokens=5, stream=bad_stream)
    rest = [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
    eng.run_until_complete(300)
    oa = eng.result(a)
    assert oa.status == "failed"
    assert "stream callback" in oa.status_reason
    for rid, p in zip(rest, prompts[1:]):
        out = eng.result(rid)
        assert out.status == "finished"
        np.testing.assert_array_equal(out.tokens, _want(gpt, p))
    m = eng.metrics_dict()
    assert m["step_retries"] == 0          # contained, never retried
    assert m["quarantines"] == 0
    assert_accounting(eng, [a] + rest)


def test_reentrant_cancel_from_stream_callback(gpt):
    """A stream callback that cancels a SIBLING mid-harvest
    (first-of-N-wins clients) must not break the harvest loop: the
    vanished slot is skipped, remaining slots keep their tokens from
    the same readback, and nothing reaches the watchdog."""
    eng, _ = make_engine(gpt)
    prompts = _prompts(32, (4, 6, 8))
    rids = {}

    def winner_stream(req, tok):
        if len(req.tokens) == 2:       # first-past-2-tokens cancels rest
            for other in (rids["b"], rids["c"]):
                eng.cancel(other)

    rids["a"] = eng.submit(prompts[0], max_new_tokens=5,
                           stream=winner_stream)
    rids["b"] = eng.submit(prompts[1], max_new_tokens=5)
    rids["c"] = eng.submit(prompts[2], max_new_tokens=5)
    eng.run_until_complete(300)
    oa = eng.result(rids["a"])
    assert oa.status == "finished"
    np.testing.assert_array_equal(oa.tokens, _want(gpt, prompts[0]))
    assert eng.result(rids["b"]).status == "cancelled"
    assert eng.result(rids["c"]).status == "cancelled"
    m = eng.metrics_dict()
    assert m["step_retries"] == 0 and m["faults"] == 0
    assert_accounting(eng, list(rids.values()))


def test_quarantine_settles_finished_but_unevicted(gpt):
    """A request that completed normally (eos/length) but was not yet
    evicted when the quarantine hit must settle as terminal `finished`,
    not `failed` — and never as finished-with-no-status."""
    eng, _ = make_engine(gpt, num_slots=2)
    a = eng.submit(_prompts(27, (4,))[0], max_new_tokens=8)
    b = eng.submit(_prompts(27, (6,))[0], max_new_tokens=8)
    for _ in range(2):
        eng.step()                         # both decoding
    req = eng._requests[a]
    assert not req.finished
    req.finished, req.finish_reason = True, "eos"   # harvested eos,
    eng.core._quarantine("test: simulated spent retry budget")  # not yet
    oa, ob = eng.result(a), eng.result(b)           # evicted
    assert oa.status == "finished" and oa.status_reason == "eos"
    assert ob.status == "failed" and "quarantine" in ob.status_reason
    eng.run_until_complete(200)
    assert_accounting(eng, [a, b])


def test_quarantine_rebuild_honors_prefix_bypass(gpt):
    """Once the ladder bypassed the prefix cache, a quarantine rebuild
    must not re-allocate the block slab nothing will ever touch."""
    eng, faults = make_engine(gpt, num_slots=2, block_len=8)
    r = eng.submit(_prompts(28, (12,))[0], max_new_tokens=2)
    eng.run_until_complete(100)
    faults.enable("gather", times=2)       # ladder_threshold=2 -> bypass
    try:
        rids = [eng.submit(np.concatenate(
            [_prompts(28, (12,))[0], s]), max_new_tokens=2)
            for s in _prompts(29, (4, 4))]
        eng.run_until_complete(200)
    finally:
        faults.disable("gather")
    assert "prefix_cache" in eng.degraded_subsystems
    assert eng.core.prefix_cache is not None     # pre-rebuild slab stays
    eng.core._quarantine("test: rebuild under bypass")
    assert eng.core.prefix_cache is None         # not re-allocated
    assert eng.core.block_pool is None
    r2 = eng.submit(_prompts(30, (5,))[0], max_new_tokens=3)
    eng.run_until_complete(200)                  # still serves correctly
    out = eng.result(r2)
    assert out.status == "finished"
    np.testing.assert_array_equal(out.tokens,
                                  _want(gpt, _prompts(30, (5,))[0], 3))


def test_cancel_unknown_id_is_loud(gpt):
    eng, _ = make_engine(gpt)
    with pytest.raises(KeyError, match="unknown request_id"):
        eng.cancel(12345)
    r = eng.submit(_prompts(31, (4,))[0], max_new_tokens=2)
    eng.run_until_complete(100)
    eng.purge(r)
    with pytest.raises(KeyError, match="already purged"):
        eng.cancel(r)


def test_chaos_smoke_artifacts(tmp_path):
    """Tier-1 artifact smoke (mirrors test_obs_dump_artifacts): one
    injected-fault scenario end-to-end through scripts/chaos_smoke.py,
    emitting a passing accounting verdict + parsing metrics.prom."""
    import importlib.util
    import json
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chaos_smoke", os.path.join(repo, "scripts", "chaos_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "artifacts")
    assert mod.main(["--out", out, "--requests", "4"]) == 0
    with open(os.path.join(out, "chaos.json")) as f:
        v = json.load(f)
    assert v["all_terminal"] and v["pools_at_baseline"]
    assert v["fired"] >= 1 and v["step_retries"] >= 1
    assert {r["status"] for r in v["requests"]} <= TERMINAL
    prom = open(os.path.join(out, "metrics.prom")).read()
    assert "serving_faults" in prom
    assert "serving_health_state" in prom


def test_fault_events_land_in_obs(gpt):
    """The obs wiring: fault / retry / degrade / quarantine / health
    transitions become discrete tracer events + gauges."""
    eng, faults = make_engine(gpt, retries=1, num_slots=2)
    tracer = eng.tracer
    tracer.enable()
    faults.enable("step", times=2)       # 1 retry + quarantine
    try:
        rids = [eng.submit(p, max_new_tokens=3)
                for p in _prompts(25, (4, 5))]
        eng.run_until_complete(200)
    finally:
        faults.disable("step")
        tracer.disable()
    names = {e[0] for e in tracer.events()}
    assert {"fault", "step_retry", "quarantine_enter",
            "quarantine_leave", "health_state"} <= names
    m = eng.metrics_dict()
    assert m["quarantines"] == 1 and m["health_state"] == 0.0
    assert_accounting(eng, rids)


# ------------------------------------------- speculative decoding (18)

def test_spec_verify_fault_ladder_disables_speculation(gpt):
    """ISSUE 18: ``spec_verify`` faults feed the degradation ladder; at
    threshold speculation is disabled ENGINE-LIFETIME and the engine
    keeps serving one committed token per step.  Matched sampling makes
    the mid-run disable invisible in tokens — the stream stays
    token-for-token ``generate()`` even though some of it was committed
    by the verify program and the rest by plain decode."""
    eng, faults = make_engine(gpt, spec_k=3)
    assert eng.core.spec_on and eng.spec_fallback_reason is None
    eng.tracer.enable()
    # cyclic prompts: the per-slot n-gram tables propose from step one,
    # so the speculative phase (and its fault point) actually runs
    prompts = [np.tile([5, 6, 7, 8], 6), np.tile([9, 10, 11], 8),
               np.tile([3, 4], 10)]
    faults.enable("spec_verify", times=2)     # == ladder threshold
    try:
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run_until_complete(300)
    finally:
        faults.disable("spec_verify")
        eng.tracer.disable()
    assert faults.fired["spec_verify"] == 2
    assert "spec_verify" in eng.degraded_subsystems
    assert eng.core.spec_bypass and not eng.spec_on
    assert eng.spec_fallback_reason.startswith("degraded:")
    assert eng.health.state == "degraded"
    assert {"fault", "degrade", "spec_disable"} <= \
        {e[0] for e in eng.tracer.events()}
    for rid, p in zip(rids, prompts):
        out = eng.result(rid)
        assert out.status == "finished"
        np.testing.assert_array_equal(out.tokens, _want(gpt, p, 8))
    assert_accounting(eng, rids)
    m = eng.metrics_dict()
    assert m["degradation_level"] == 1
    # engine-lifetime: a fresh cyclic prompt drafts NOTHING after the
    # rung applies — the draft counter stays where the disable left it
    drafted = m["spec_draft_tokens"]
    r = eng.submit(np.tile([7, 8, 9], 8), max_new_tokens=6)
    eng.run_until_complete(200)
    assert eng.result(r).status == "finished"
    assert eng.metrics_dict()["spec_draft_tokens"] == drafted
    assert_accounting(eng, rids + [r])
