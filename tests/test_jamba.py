"""Jamba (models/jamba.py), the hybrid of state-space and attention
layers, on the CPU at a small size (hidden 64, 4 query heads / 1 KV head
x 16, 4 layers with attention at 1 and 3, ``d_conv`` 4, float32) against
the plain reference of ``benchmarks/builders/jamba.py``, which shares no
code with the model: the full forward, and prefill then ragged cached
decode through ``ServingEngine``, unchunked and in chunks smaller than
the prompts, LOGITS against logits.  The comparison has to fail on a
state updated past ``valid``, a carry dropped between chunks, a
convolution window taken at the chunk's width, attention layers at the
wrong indices and missing inner norms; the pool has to hold the
recurrent state beside the KV planes and leave the other families
without one."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from benchmarks.builders import jamba as builder
from benchmarks.lib.reference import F32_LOGIT_TOL
from paddle_tpu.models import (GPTForCausalLM, JambaConfig,
                               JambaForCausalLM, LlamaForCausalLM,
                               OuroForCausalLM, gpt_tiny, jamba_tiny,
                               llama_tiny, ouro_tiny)
from paddle_tpu.models import jamba as jamba_model
from paddle_tpu.nn.functional_call import state
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_pool import (KVPool, cache_geometry,
                                        recurrent_state_spec, state_bytes)

VOCAB = 128


def file_config(cfg: JambaConfig) -> dict:
    """The configuration-file form the reference reads."""
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads,
            "attn_layer_period": cfg.attn_layer_period,
            "attn_layer_offset": cfg.attn_layer_offset,
            "mamba_d_state": cfg.mamba_d_state,
            "mamba_d_conv": cfg.mamba_d_conv,
            "mamba_expand": cfg.mamba_expand,
            "mamba_dt_rank": cfg.mamba_dt_rank,
            "mamba_conv_bias": cfg.mamba_conv_bias,
            "mamba_proj_bias": cfg.mamba_proj_bias,
            "rms_norm_eps": cfg.rms_norm_eps, "torch_dtype": cfg.dtype,
            "num_experts": 1, "tie_word_embeddings": True,
            "hidden_act": "silu", "sliding_window": None,
            "max_position_embeddings": cfg.max_seq_len}


def make_model(seed=0, **cfg_kw):
    """A seeded model whose norm weights, convolution bias and ``D``
    are NOT their initial ones and zeros, so that each matters."""
    paddle_tpu.seed(seed)
    model = JambaForCausalLM(jamba_tiny(**cfg_kw))
    model.eval()
    params, _ = state(model)
    key = jax.random.key(seed + 100)
    moved = {}
    for i, (name, p) in enumerate(sorted(params.items())):
        if "layernorm" in name or name.endswith(("conv_bias", ".D")):
            moved[name] = p + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), p.shape, p.dtype)
    model.set_state_dict(moved)
    return model


def reference(model, ids):
    return np.asarray(builder.reference_forward(
        file_config(model.cfg), state(model)[0], jnp.asarray(ids)))


def rel_err(got, ref):
    return float(np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref)))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, VOCAB, (2, 37),
                                             dtype=np.int32)


# ------------------------------------------------------------ the forward

def test_forward_agrees_with_the_reference(ids):
    model = make_model()
    assert rel_err(model(jnp.asarray(ids)), reference(model, ids)) \
        <= F32_LOGIT_TOL


@pytest.mark.parametrize("b, w, d, n", [(1, 256, 1024, 16), (2, 40, 2048, 4),
                                        (1, 16, 1024, 16)])
def test_the_scan_kernel_is_the_sequential_scan(b, w, d, n):
    """``pallas_chunk`` (interpreted here) against the definition: a
    width of several kernel chunks, one its chunk does not divide, a
    batch, and padding by ``delta = 0`` that leaves the state alone."""
    from paddle_tpu.kernels.selective_scan import (
        scan_route, selective_scan, selective_scan_reference)
    ks = jax.random.split(jax.random.key(w), 6)
    u = jax.random.normal(ks[0], (b, w, d))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, w, d)) - 2)
    delta = delta.at[:, w - 3:].set(0.0)
    a = -jnp.exp(jax.random.normal(ks[2], (n, d)))
    bm, cm = (jax.random.normal(k, (b, w, n)) for k in ks[3:5])
    h0 = jax.random.normal(ks[5], (b, n, d))
    assert scan_route(w, d) == ("pallas_chunk", None)
    y, h = jax.jit(selective_scan)(u, delta, a, bm, cm, h0)
    want_y, want_h = selective_scan_reference(u, delta, a, bm, cm, h0)
    assert rel_err(y, np.asarray(want_y)) <= 1e-6
    assert rel_err(h, np.asarray(want_h)) <= 1e-6
    before, _ = selective_scan_reference(
        u[:, :w - 3], delta[:, :w - 3], a, bm[:, :w - 3], cm[:, :w - 3], h0)
    assert rel_err(h, np.asarray(_)) <= 1e-6       # the padding moved nothing


def test_forward_through_the_scan_kernel():
    """``d_inner`` 1024 takes the kernel in the model (the tiny size's
    128 channels take the sequential scan everywhere)."""
    model = make_model(hidden_size=512, num_layers=2)
    assert model.recurrence_route(24) == ("pallas_chunk", None)
    ids = np.random.default_rng(3).integers(0, VOCAB, (2, 24),
                                            dtype=np.int32)
    assert rel_err(model(jnp.asarray(ids)), reference(model, ids)) \
        <= F32_LOGIT_TOL
    forced = make_model(hidden_size=512, num_layers=2,
                        scan_form="sequential")
    assert forced.recurrence_route(24) == ("sequential", "forced")
    assert rel_err(forced(jnp.asarray(ids)), reference(model, ids)) \
        <= F32_LOGIT_TOL


def test_padded_chunks_with_a_carried_state_give_the_forward(ids):
    """``decode_step`` alone: right-padded chunks from a carried state,
    then token by token."""
    model = make_model()
    ref = reference(model, ids)
    step = jax.jit(model.decode_step)
    caches, st, pos = model.init_cache(2, 64), model.init_state(2), 0
    for width, valid in ((16, 16), (16, 11), (8, 5)):
        chunk = np.zeros((2, width), np.int32)
        chunk[:, :valid] = ids[:, pos:pos + valid]
        logits, caches, st = step(jnp.asarray(chunk), caches, pos,
                                  state=st, valid=valid)
        assert rel_err(logits[:, :valid], ref[:, pos:pos + valid]) \
            <= F32_LOGIT_TOL
        pos += valid
    for i in range(pos, 37):
        logits, caches, st = step(jnp.asarray(ids[:, i:i + 1]), caches, i,
                                  state=st)
        assert rel_err(logits[:, 0], ref[:, i]) <= F32_LOGIT_TOL


def test_published_sizes():
    cfg = JambaConfig()
    # ISSUE 32: 26 x 104,161,472 + 2 x 76,682,240 + 167,772,160 + 2,560
    assert cfg.num_params() == 3_029_337_472
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "attention"] \
        == [7, 21]
    assert cfg.num_cache_layers == 2 and cfg.num_state_layers == 26
    assert (cfg.d_inner, cfg.head_dim, cfg.kv_heads) == (5120, 128, 1)
    model = make_model()
    assert sum(p.size for p in state(model)[0].values()) \
        == model.cfg.num_params()
    assert model.cfg.layer_kinds == ("mamba", "attention") * 2


# -------------------------------------------------- through ServingEngine

class Spy:
    """Logits of every ``decode_step`` call the engine's programs make,
    handed to the host by a callback in the program."""

    def __init__(self, model):
        self.calls = []
        inner = model.decode_step

        def spied(input_ids, caches, position, state=None, valid=None):
            logits, new, st = inner(input_ids, caches, position,
                                    state=state, valid=valid)
            jax.debug.callback(
                lambda l, p: self.calls.append((np.asarray(l),
                                                np.asarray(p))),
                logits, position)
            return logits, new, st

        model.decode_step = spied


def serve(model, prompts, new_tokens, late=2, **engine_kw):
    """Serve ``prompts`` as one ragged batch, the last ``late`` of them
    submitted two steps after the others.  Returns per request its
    tokens, the logits that produced each of them (the prefill's last
    position, then one decode row a token) and the prefill's logits over
    the prompt."""
    spy = Spy(model)
    engine_kw.setdefault("enable_prefix_cache", False)
    eng = ServingEngine(model, num_slots=3, min_bucket=8, max_seq=96,
                        **engine_kw)
    try:
        first = len(prompts) - late
        rids = [eng.submit(p, max_new_tokens=new_tokens)
                for p in prompts[:first]]
        slot_of, steps = {}, 0
        while True:
            more = eng.step()
            steps += 1
            jax.effects_barrier()
            for slot, st in eng.core._slots.items():
                slot_of.setdefault(st.req.request_id, slot)
            if steps == 2:
                rids += [eng.submit(p, max_new_tokens=new_tokens)
                         for p in prompts[first:]]
                more = True
            if not more:
                break
        outs = [eng.result(r) for r in rids]
        plans = [eng.core.scheduler.chunk_plan(0, len(p),
                                               eng.core.prefill_chunk)
                 for p in prompts]
    finally:
        eng.close()
    assert all(o.status == "finished" for o in outs)
    prefills = [c for c in spy.calls if c[1].ndim == 0]
    decodes = [c for c in spy.calls if c[1].ndim == 1]
    assert len(prefills) == sum(len(plan) for plan in plans)    # FCFS
    served = []
    for p, out, rid, plan in zip(prompts, outs, rids, plans):
        mine, prefills = prefills[:len(plan)], prefills[len(plan):]
        over = np.concatenate([lg[0, :valid] for (lg, off), (o, _, valid)
                               in zip(mine, plan) if int(off) == o])
        assert len(over) == len(p)
        rows = [over[-1]]
        for lg, pos in decodes:
            j = int(pos[slot_of[rid]]) - len(p)
            if 0 <= j < new_tokens - 1 and len(rows) == j + 1:
                rows.append(lg[slot_of[rid], 0])
        assert len(rows) == new_tokens
        served.append((list(out.tokens), np.stack(rows), over))
    return served


def prompts_of(lengths, seed=1):
    rs = np.random.default_rng(seed)
    return [rs.integers(0, VOCAB, n, dtype=np.int32) for n in lengths]


# lengths that fill no bucket, longer and shorter than the chunk
LENGTHS = (37, 5, 21, 44)
CHUNKING = [None, 16]


def engine_err(model, served, prompts):
    """Worst ``rel_err`` of the served logits against the reference's
    full forward over prompt + emitted tokens."""
    worst = 0.0
    for p, (tokens, rows, over) in zip(prompts, served):
        seq = np.concatenate([p, np.asarray(tokens[:-1], np.int32)])
        ref = reference(model, seq[None])[0]
        worst = max(worst, rel_err(over, ref[:len(p)]),
                    rel_err(rows, ref[len(p) - 1:]))
    return worst


@pytest.mark.parametrize("chunk", CHUNKING)
def test_engine_prefill_and_decode_agree_with_the_reference(chunk):
    model = make_model()
    prompts = prompts_of(LENGTHS)
    served = serve(model, prompts, 6, prefill_chunk=chunk)
    assert engine_err(model, served, prompts) <= F32_LOGIT_TOL
    # the engine's tokens are the greedy ones of generate()
    for p, (tokens, _, _) in zip(prompts, served):
        want = np.asarray(model.generate(p[None], max_new_tokens=6))
        assert tokens == want[0, len(p):].tolist()


# ---- the five wrong builds: each returns (the faulty model, the model
# whose weights the reference is given)

def state_updated_past_valid(monkeypatch):
    monkeypatch.setattr(jamba_model, "_mask_padding",
                        lambda delta, valid: delta)
    return make_model(), make_model()


def window_taken_at_width(monkeypatch):
    inner = jamba_model._window_after
    monkeypatch.setattr(jamba_model, "_window_after",
                        lambda xx, valid, keep: inner(xx, None, keep))
    return make_model(), make_model()


def carry_dropped_between_chunks(monkeypatch):
    model = make_model()
    inner = model.decode_step

    def forgetful(input_ids, caches, position, state=None, valid=None):
        if input_ids.shape[1] > 1:          # a prefill chunk
            state = jax.tree_util.tree_map(jnp.zeros_like, state)
        return inner(input_ids, caches, position, state=state, valid=valid)

    model.decode_step = forgetful
    return model, make_model()


def attention_at_the_wrong_indices(monkeypatch):
    """Attention at layers 0 and 2 instead of 1 and 3, every layer's
    weights moved with it: the right layers in the wrong order."""
    right = make_model()
    wrong = JambaForCausalLM(jamba_tiny(attn_layer_offset=0))
    wrong.eval()
    swap = {1: 0, 0: 1, 3: 2, 2: 3}
    moved = {}
    for name, p in state(right)[0].items():
        parts = name.split(".")
        if parts[:2] == ["jamba", "layers"]:
            parts[2] = str(swap[int(parts[2])])
        moved[".".join(parts)] = p
    wrong.set_state_dict(moved)
    return wrong, right


def inner_norms_dropped(monkeypatch):
    model = make_model()
    for layer in model.jamba.layers:
        if layer.kind == "mamba":
            for norm in (layer.mamba.dt_layernorm, layer.mamba.b_layernorm,
                         layer.mamba.c_layernorm):
                norm.forward = lambda x: x
    return model, make_model()


FAULTS = [state_updated_past_valid, window_taken_at_width,
          carry_dropped_between_chunks, attention_at_the_wrong_indices,
          inner_norms_dropped]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_comparison_fails_on(fault, monkeypatch):
    """The same comparison as above, in chunks, on a model with one
    fault."""
    model, right = fault(monkeypatch)
    prompts = prompts_of(LENGTHS)
    served = serve(model, prompts, 6, prefill_chunk=16)
    assert engine_err(right, served, prompts) > 50 * F32_LOGIT_TOL


@pytest.mark.parametrize("fault", [state_updated_past_valid,
                                   window_taken_at_width])
def test_padding_faults_show_without_chunking_too(fault, monkeypatch):
    """One bucketed chunk a prompt is right-padded as well."""
    model, right = fault(monkeypatch)
    prompts = prompts_of(LENGTHS)
    served = serve(model, prompts, 6)
    assert engine_err(right, served, prompts) > 50 * F32_LOGIT_TOL


def test_a_slot_freed_and_adopted_again_serves_as_a_fresh_engine():
    """One slot: the second request takes the row the first left (its
    KV rows and its recurrent state, advanced since by nothing) and must
    read none of it."""
    first, second = prompts_of((30, 19), seed=5)
    model = make_model()
    eng = ServingEngine(model, num_slots=1, min_bucket=8, max_seq=96,
                        prefill_chunk=16, enable_prefix_cache=False)
    try:
        eng.serve_batch([first], max_new_tokens=9)
        again = eng.serve_batch([second], max_new_tokens=9)[0]
    finally:
        eng.close()
    fresh = serve(make_model(), [second], 9, late=0, prefill_chunk=16)[0]
    assert list(again.tokens) == fresh[0]
    want = np.asarray(model.generate(second[None], max_new_tokens=9))
    assert list(again.tokens) == want[0, len(second):].tolist()


def test_parked_slots_stay_finite_over_two_thousand_steps(monkeypatch):
    """A free slot and one whose request finished ride along in every
    decode step: their state rows advance on whatever token their row
    last held, for as long as a run has steps, and stay finite; a
    request adopted into such a row afterwards is served as by a fresh
    engine.  (Attention by the dense XLA form: the interpreted kernel
    takes a sixth of a second a step here, and is not what is tested.)"""
    from paddle_tpu.core.flags import flags
    monkeypatch.setattr(flags, "pallas_routing", "never")
    model = make_model(max_seq_len=2200)
    long_p, short_p, late_p = prompts_of((12, 9, 17), seed=7)
    eng = ServingEngine(model, num_slots=3, min_bucket=8, max_seq=2200,
                        enable_prefix_cache=False)
    try:
        long_id = eng.submit(long_p, max_new_tokens=2050)
        short_id = eng.submit(short_p, max_new_tokens=3)
        eng.run_until_complete(2200)
        assert eng.result(long_id).status == "finished"
        assert len(eng.result(long_id).tokens) == 2050
        assert eng.core._step_index >= 2000
        for leaf in jax.tree_util.tree_leaves(eng.core.pool.state):
            assert bool(jnp.all(jnp.isfinite(leaf))), "a parked state row"
        # slot 2 was never adopted, slot 1 parked after 3 tokens
        late = eng.serve_batch([late_p, late_p, late_p], max_new_tokens=5)
    finally:
        eng.close()
    want = np.asarray(model.generate(late_p[None], max_new_tokens=5))
    for out in late:
        assert list(out.tokens) == want[0, len(late_p):].tolist()
    assert eng.result(short_id).status == "finished"


# ------------------------------------------------------------- refusals

def test_the_engine_refuses_what_a_recurrent_state_forbids():
    model = make_model()
    with pytest.raises(ValueError, match="recurrent state at its boundary"):
        ServingEngine(model, num_slots=2, max_seq=64)   # the cache's default
    with pytest.raises(ValueError, match="enable_prefix_cache=True"):
        ServingEngine(model, num_slots=2, max_seq=64,
                      enable_prefix_cache=True)
    with pytest.raises(ValueError, match="tensor_parallel 2: 1 KV head "
                                         "cannot partition"):
        ServingEngine(make_model(), num_slots=2, max_seq=64,
                      enable_prefix_cache=False, tensor_parallel=2)
    eng = ServingEngine(model, num_slots=2, min_bucket=8, max_seq=64,
                        enable_prefix_cache=False, spec_k=3,
                        fused_decode=True)
    try:
        assert not eng.core.spec_on
        assert "cannot be rolled back" in eng.core.spec_fallback_reason
        assert eng.decode_path == "unfused"
        assert "state-space layers" in eng.decode_fallback_reason
        # and it serves, one token a step, through the plain program
        prompt = prompts_of((20,))[0]
        out = eng.serve_batch([prompt], max_new_tokens=4)[0]
        want = np.asarray(model.generate(prompt[None], max_new_tokens=4))
        assert list(out.tokens) == want[0, 20:].tolist()
        assert eng.core.trace_counts["verify"] == 0
    finally:
        eng.close()


def test_the_aot_store_refuses_a_recurrent_state(tmp_path):
    from paddle_tpu.serving.aot import AOTStore, AOTStoreError
    from scripts import aot_build
    assert aot_build.main(["build", str(tmp_path), "--model", "gpt_tiny",
                           "--seed", "0"]) == 0
    store = AOTStore.open(str(tmp_path))
    try:
        with pytest.raises(AOTStoreError, match="no recurrent state "
                                                "operands"):
            ServingEngine(make_model(), num_slots=2, max_seq=64,
                          enable_prefix_cache=False, aot_store=store)
    finally:
        store.close()
    from paddle_tpu.serving.aot import build_engine_store
    eng = ServingEngine(make_model(), num_slots=2, max_seq=64,
                        enable_prefix_cache=False)
    try:
        with pytest.raises(AOTStoreError, match="no recurrent state "
                                                "operands"):
            build_engine_store(str(tmp_path / "second"), eng.core)
    finally:
        eng.close()


def test_generate_refuses_ragged_prompts():
    model = make_model()
    with pytest.raises(ValueError, match="advance the recurrent state"):
        model.generate(jnp.zeros((2, 8), jnp.int32), 2,
                       prompt_lens=jnp.asarray([8, 5]))


# ------------------------------------------------------- pools and spans

def _family(name):
    return {"gpt": lambda: GPTForCausalLM(gpt_tiny()),
            "llama": lambda: LlamaForCausalLM(llama_tiny()),
            "ouro": lambda: OuroForCausalLM(ouro_tiny()),
            "jamba": lambda: JambaForCausalLM(jamba_tiny())}[name]()


@pytest.mark.parametrize("family, geometry, leaves, nbytes", [
    ("gpt", (2, 2, 4), 0, 0), ("llama", (2, 2, 2), 0, 0),
    ("ouro", (9, 1, 36), 0, 0),
    # 2 attention layers of 1 KV head; 2 Mamba layers x (8 x 128 x 4 B of
    # state + 3 x 128 x 4 B of window)
    ("jamba", (2, 2, 1), 4, 2 * (8 * 128 * 4 + 3 * 128 * 4))])
def test_cache_geometry_and_state_for_four_families(family, geometry,
                                                    leaves, nbytes):
    paddle_tpu.seed(0)
    model = _family(family)
    assert cache_geometry(model.cfg) == geometry
    spec = recurrent_state_spec(model)
    assert len(jax.tree_util.tree_leaves(spec)) == leaves
    assert state_bytes(spec) == nbytes
    pool = KVPool.create(model, num_slots=3, max_seq=32)
    assert pool.planes == geometry[0] and len(pool.ks) == geometry[1]
    assert pool.state_bytes_per_slot == nbytes
    got = jax.tree_util.tree_leaves(pool.state)
    assert [a.shape[0] for a in got] == [3] * leaves
    assert sum(a.nbytes for a in got) == 3 * nbytes
    if family == "jamba":
        assert pool.state[0]["ssm"].shape == (3, 8, 128)
        assert pool.state[0]["ssm"].dtype == jnp.float32
        assert pool.state[1]["conv"].shape == (3, 3, 128)


def test_spans_counts_and_the_gauge():
    """``state_slots`` on the step span, ``state_carried`` on the
    request lane's chunks, ``scan_route`` on the ``decode_block`` event,
    the gauge beside ``serving.kv.planes``; zeros and blanks for a model
    without a recurrent state."""
    model = make_model()
    eng = ServingEngine(model, num_slots=3, min_bucket=8, max_seq=96,
                        prefill_chunk=16, enable_prefix_cache=False)
    try:
        eng.serve_batch(prompts_of((37, 5)), max_new_tokens=4)
        snap = eng.registry.snapshot()
        assert snap["serving.state.bytes_per_slot"] == 2 * (4096 + 1536)
        assert snap["serving.kv.planes"] == 2
        steps = [s for s in eng.tracer.spans(lane=0)
                 if s.name == "serving.step"]
        decoding = [s for s in steps if s.attrs["active_slots"]]
        assert decoding and all(s.attrs["state_slots"] == 3
                                for s in decoding)
        assert all(s.attrs["state_slots"] == 0 for s in steps
                   if not s.attrs["active_slots"])
        chunks = [s for s in eng.tracer.spans()
                  if s.name == "prefill_chunk"]
        carried = [(s.attrs["request"], s.attrs["chunk"],
                    s.attrs["state_carried"]) for s in chunks]
        assert all(c == (chunk > 0) for _, chunk, c in carried)
        assert sum(c for _, _, c in carried) == 2      # 37 = 16 + 16 + 5
        event = eng.tracer.events("decode_block")[0][3]
        assert event["scan_route"] == "prefill=sequential,decode=one_step"
        assert "d_inner 128" in event["scan_reason"]
        assert eng.core.scan_route()[0] == event["scan_route"]
    finally:
        eng.close()
    paddle_tpu.seed(0)
    gpt = ServingEngine(GPTForCausalLM(gpt_tiny()), num_slots=2,
                        min_bucket=8, max_seq=64)
    try:
        gpt.serve_batch(prompts_of((9,)), max_new_tokens=3)
        assert gpt.registry.snapshot()["serving.state.bytes_per_slot"] == 0
        assert all(s.attrs["state_slots"] == 0
                   for s in gpt.tracer.spans(lane=0)
                   if s.name == "serving.step")
        assert all(s.attrs["state_carried"] is False
                   for s in gpt.tracer.spans() if s.name == "prefill_chunk")
        event = gpt.tracer.events("decode_block")[0][3]
        assert event["scan_route"] == "" and event["scan_reason"] == ""
    finally:
        gpt.close()
