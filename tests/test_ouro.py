"""Ouro (models/ouro.py), the looped decoder, on the CPU at a small size
(hidden 64, 4 heads x 16, 3 layers, 3 passes, vocabulary 128, float32)
against the plain reference of ``benchmarks/builders/ouro.py``, which
shares no code with the model: the full forward, and prefill then decode
through ``ServingEngine`` as a ragged batch, LOGITS against logits.  The
comparison has to fail on a wrong KV plane, a dropped pass and a missing
norm; the serving pools have to count planes, not layers, and leave GPT's
and Llama's geometry as it was."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from benchmarks.builders import ouro as builder
from benchmarks.lib.reference import F32_LOGIT_TOL
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,
                               OuroConfig, OuroForCausalLM, gpt_tiny,
                               llama_tiny, ouro_tiny)
from paddle_tpu.models import ouro as ouro_model
from paddle_tpu.nn.functional_call import state
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_pool import BlockPool, KVPool, cache_geometry

VOCAB = 128


def file_config(cfg: OuroConfig) -> dict:
    """The configuration-file form the reference reads."""
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "total_ut_steps": cfg.total_ut_steps,
            "early_exit_threshold": cfg.early_exit_threshold,
            "torch_dtype": cfg.dtype, "max_position_embeddings": 128,
            "tie_word_embeddings": False}


def make_model(seed=0, **cfg_kw):
    """A seeded model whose norm weights and gate are NOT their
    initial ones and zeros, so that every norm and the gate matter."""
    paddle_tpu.seed(seed)
    model = OuroForCausalLM(ouro_tiny(**cfg_kw))
    model.eval()
    params, _ = state(model)
    key = jax.random.key(seed + 100)
    moved = {}
    for i, (name, p) in enumerate(sorted(params.items())):
        if "layernorm" in name or name.endswith("norm.weight") \
                or "early_exit_gate.bias" in name:
            moved[name] = p + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), p.shape, p.dtype)
    model.set_state_dict(moved)
    return model


def reference(model, ids, **cfg_kw):
    cfg = file_config(model.cfg)
    cfg.update(cfg_kw)
    return np.asarray(builder.reference_forward(
        cfg, state(model)[0], jnp.asarray(ids)))


def rel_err(got, ref):
    return float(np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref)))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, VOCAB, (2, 24),
                                             dtype=np.int32)


# ------------------------------------------------------------ the forward

def test_forward_agrees_with_the_reference(ids):
    model = make_model()
    assert rel_err(model(jnp.asarray(ids)), reference(model, ids)) \
        <= F32_LOGIT_TOL


def test_cached_decode_agrees_with_the_reference(ids):
    """``decode_step`` alone: a 16-token chunk, then token by token."""
    model = make_model()
    ref = reference(model, ids)
    step = jax.jit(model.decode_step)
    logits, caches = step(jnp.asarray(ids[:, :16]), model.init_cache(2, 64),
                          0)
    assert rel_err(logits, ref[:, :16]) <= F32_LOGIT_TOL
    for i in range(16, 24):
        logits, caches = step(jnp.asarray(ids[:, i:i + 1]), caches, i)
        assert rel_err(logits[:, 0], ref[:, i]) <= F32_LOGIT_TOL
    (k, v, pos), = caches
    assert int(pos) == 24 and k.shape == (2, 64, 9 * 4, 16)


def test_one_pass_is_the_same_stack_run_once(ids):
    """``total_ut_steps`` 1 on the same weights: the stack, the final
    norm and the head once.  It differs from three passes."""
    looped = make_model()
    once = OuroForCausalLM(ouro_tiny(total_ut_steps=1))
    once.eval()
    once.set_state_dict(state(looped)[0])
    got = once(jnp.asarray(ids))
    assert rel_err(got, reference(looped, ids, total_ut_steps=1)) \
        <= F32_LOGIT_TOL
    assert rel_err(got, reference(looped, ids)) > 0.05
    assert once.cfg.num_cache_layers == looped.cfg.num_layers == 3


def test_the_gate_chooses_the_pass_below_threshold_one(ids):
    """At the published threshold 1 the output is the last pass; at a
    lower one tokens leave earlier, and model and reference agree on
    which."""
    model = make_model()
    early = OuroForCausalLM(ouro_tiny(early_exit_threshold=0.6))
    early.eval()
    early.set_state_dict(state(model)[0])
    got = early(jnp.asarray(ids))
    assert rel_err(got, reference(early, ids)) <= F32_LOGIT_TOL
    assert rel_err(got, reference(model, ids)) > 0.01


def test_parameter_counts():
    cfg = OuroConfig()
    # ISSUE 28: 48 x 51,388,416 + 2 x 100,663,296 + 2,048 + 2,049
    assert cfg.num_params() == 2_667_974_657
    model = make_model()
    assert sum(p.size for p in state(model)[0].values()) \
        == model.cfg.num_params()
    assert cfg.num_cache_layers == 192 and cfg.loop_passes == 4


# -------------------------------------------------- through ServingEngine

class Spy:
    """Logits of every ``decode_step`` call the engine's programs make,
    handed to the host by a callback in the program."""

    def __init__(self, model):
        self.calls = []
        inner = model.decode_step

        def spied(input_ids, caches, position):
            logits, new = inner(input_ids, caches, position)
            jax.debug.callback(
                lambda l, p: self.calls.append((np.asarray(l),
                                                np.asarray(p))),
                logits, position)
            return logits, new

        model.decode_step = spied


def serve(model, prompts, new_tokens, **engine_kw):
    """Serve ``prompts`` as one ragged batch.  Returns per request its
    tokens and the logits that produced each of them: the prefill's last
    position, then one decode row a token."""
    spy = Spy(model)
    eng = ServingEngine(model, num_slots=4, min_bucket=8, max_seq=64,
                        **engine_kw)
    try:
        rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        slot_of = {}
        while True:
            more = eng.step()
            jax.effects_barrier()
            for slot, st in eng.core._slots.items():
                slot_of.setdefault(st.req.request_id, slot)
            if not more:
                break
        outs = [eng.result(r) for r in rids]
        hits = [o.prefix_hit_tokens for o in outs]
    finally:
        eng.close()
    assert all(o.status == "finished" for o in outs)
    prefills = [c for c in spy.calls if c[1].ndim == 0]
    decodes = [c for c in spy.calls if c[1].ndim == 1]
    assert len(prefills) == len(prompts)        # one chunk a request, FCFS
    served = []
    for r, (p, out, rid) in enumerate(zip(prompts, outs, rids)):
        logits, offset = prefills[r]
        rows = [logits[0, len(p) - int(offset) - 1]]
        for lg, pos in decodes:
            j = int(pos[slot_of[rid]]) - len(p)
            if 0 <= j < new_tokens - 1 and len(rows) == j + 1:
                rows.append(lg[slot_of[rid], 0])
        assert len(rows) == new_tokens
        served.append((list(out.tokens), np.stack(rows),
                       logits[0, :len(p) - int(offset)]))
    return served, hits


def prompts_of(lengths, seed=1):
    rs = np.random.default_rng(seed)
    return [rs.integers(0, VOCAB, n, dtype=np.int32) for n in lengths]


def engine_err(model, served, prompts, cfg_model=None):
    """Worst ``rel_err`` of the served logits against the reference's
    full forward over prompt + emitted tokens."""
    worst = 0.0
    for p, (tokens, rows, prefill) in zip(prompts, served):
        seq = np.concatenate([p, np.asarray(tokens[:-1], np.int32)])
        ref = reference(cfg_model or model, seq[None])[0]
        worst = max(worst, rel_err(prefill, ref[:len(p)]),
                    rel_err(rows, ref[len(p) - 1:]))
    return worst


def test_engine_prefill_and_decode_agree_with_the_reference():
    model = make_model()
    prompts = prompts_of((9, 20, 33))
    served, _ = serve(model, prompts, 6, enable_prefix_cache=False)
    assert engine_err(model, served, prompts) <= F32_LOGIT_TOL
    # the engine's tokens are the greedy ones of generate()
    for p, (tokens, _, _) in zip(prompts, served):
        want = np.asarray(model.generate(p[None], max_new_tokens=6))
        assert tokens == want[0, len(p):].tolist()


def wrong_plane(monkeypatch):
    # pass t reads and writes pass t - 1's plane
    monkeypatch.setattr(
        ouro_model, "plane_index",
        lambda t, layer, n: jnp.maximum(t - 1, 0) * n + layer)
    return make_model()


def missing_norm(monkeypatch):
    # the second norm of every traced layer (N2, after the attention's
    # output projection) is left out
    inner, calls = ouro_model._norm, [0]

    def norm(x, w, eps):
        calls[0] += 1
        if calls[0] % 5 == 2:       # N1, N2, N3, N4, final: per trace
            return x.astype(jnp.float32)
        return inner(x, w, eps)

    monkeypatch.setattr(ouro_model, "_norm", norm)
    return make_model()


def dropped_pass(monkeypatch):
    short = OuroForCausalLM(ouro_tiny(total_ut_steps=2))
    short.eval()
    short.set_state_dict(state(make_model())[0])
    return short


@pytest.mark.parametrize("fault", [wrong_plane, missing_norm, dropped_pass])
def test_the_comparison_fails_on(fault, monkeypatch):
    """The same comparison as above, on a model with one fault."""
    model = fault(monkeypatch)
    prompts = prompts_of((9, 20, 33))
    served, _ = serve(model, prompts, 6, enable_prefix_cache=False)
    assert engine_err(model, served, prompts, cfg_model=make_model()) \
        > 50 * F32_LOGIT_TOL


def test_prefix_cache_hit_gives_the_logits_of_a_miss():
    """The radix cache over a looped model: the second request of the
    same prompt is served from blocks (32 of its 40 tokens) and its
    logits are those of the first."""
    model = make_model()
    prompt = prompts_of((40,))[0]
    (miss,), (hit0,) = serve(model, [prompt], 5)
    assert hit0 == 0
    spy_model = make_model()
    spy = Spy(spy_model)
    eng = ServingEngine(spy_model, num_slots=4, min_bucket=8, max_seq=64)
    try:
        outs = eng.serve_batch([prompt], max_new_tokens=5)
        first = len(spy.calls)
        outs += eng.serve_batch([prompt], max_new_tokens=5)
        jax.effects_barrier()
    finally:
        eng.close()
    assert outs[1].prefix_hit_tokens == 32 and outs[0].prefix_hit_tokens == 0
    assert list(outs[1].tokens) == list(outs[0].tokens) == miss[0]
    again = spy.calls[first:]
    logits, offset = again[0]
    assert int(offset) == 32        # only the suffix was prefilled
    assert rel_err(logits[0, 7], miss[1][0]) <= F32_LOGIT_TOL
    rows = np.stack([lg[0, 0] for lg, pos in again[1:5]])
    assert rel_err(rows, miss[1][1:]) <= F32_LOGIT_TOL


def test_engine_refuses_what_the_model_cannot_do():
    model = make_model()
    eng = ServingEngine(model, num_slots=2, min_bucket=8, max_seq=64,
                        fused_decode=True)
    try:
        assert eng.decode_path == "unfused"
        assert "sandwich" in eng.core.decode_fallback_reason
    finally:
        eng.close()
    with pytest.raises(ValueError, match="no tensor-parallel layout"):
        ServingEngine(model, num_slots=2, tensor_parallel=2)


def test_aot_store_serves_the_looped_model_without_a_trace(tmp_path):
    """The fingerprint carries the pool's resolved geometry, and a warm
    engine loads every program of a looped model: no trace, same
    tokens."""
    from paddle_tpu.serving import (AOTStore, EngineCore,
                                    build_engine_store, engine_aot_context)
    kw = dict(num_slots=2, min_bucket=8, max_seq=64)
    core = EngineCore(make_model(), **kw)
    context = engine_aot_context(core)
    assert context["kv_planes"] == 9
    build_engine_store(str(tmp_path), core)
    prompt = prompts_of((20,))
    store = AOTStore.open(str(tmp_path))
    try:
        eng = ServingEngine(make_model(), aot_store=store, **kw)
        assert eng.aot_status == "warm"
        warm = eng.serve_batch(prompt, max_new_tokens=4)[0].tokens
        assert eng.core.trace_counts \
            == {"prefill": 0, "decode": 0, "verify": 0}
        eng.close()
    finally:
        store.close()
    traced = ServingEngine(make_model(), **kw)
    try:
        assert list(warm) == list(
            traced.serve_batch(prompt, max_new_tokens=4)[0].tokens)
    finally:
        traced.close()


def test_spans_and_gauge_carry_the_passes_and_the_planes():
    looped, plain = make_model(), GPTForCausalLM(gpt_tiny())
    for model, passes, planes in ((looped, 3, 9), (plain, 1, 2)):
        eng = ServingEngine(model, num_slots=2, min_bucket=8, max_seq=64)
        try:
            eng.serve_batch(prompts_of((9,)), max_new_tokens=3)
            spans = eng.tracer.spans()
            steps = [s.attrs["loop_passes"] for s in spans
                     if s.name == "serving.step"
                     and s.attrs["active_slots"] > 0]
            assert steps and set(steps) == {passes}
            idle = [s.attrs["loop_passes"] for s in spans
                    if s.name == "serving.step"
                    and s.attrs["active_slots"] == 0]
            assert set(idle) <= {0}
            assert [s.attrs["loop_passes"] for s in spans
                    if s.name == "prefill"] == [passes]
            assert eng.registry.snapshot()["serving.kv.planes"] == planes
            eng.metrics.reset()     # an engine-lifetime fact survives
            assert eng.registry.snapshot()["serving.kv.planes"] == planes
        finally:
            eng.close()


# ------------------------------------------------------------- the pools

@pytest.mark.parametrize("make, planes, slabs, slab_heads, model_heads", [
    (lambda: GPTForCausalLM(gpt_tiny()), 2, 2, 4, 4),
    (lambda: LlamaForCausalLM(llama_tiny()), 2, 2, 2, 2),
    (lambda: OuroForCausalLM(ouro_tiny()), 9, 1, 36, 4),
])
def test_pools_count_planes(make, planes, slabs, slab_heads, model_heads):
    """GPT's and Llama's pools are what they were (a slab a layer); the
    looped model's 9 planes live in one slab of 9 x 4 heads.  A cached
    row costs 2 x planes x kv_heads x head_dim x itemsize in each."""
    model = make()
    cfg = model.cfg
    assert cache_geometry(cfg) == (planes, slabs, slab_heads)
    pool = KVPool.create(model, 3, max_seq=32)
    blocks = BlockPool.create(model, 5, 8, 32)
    assert pool.planes == planes and pool.num_layers == slabs
    assert len(pool.ks) == len(pool.vs) == len(blocks.bks) == slabs
    assert pool.ks[0].shape == (3, 32, slab_heads, cfg.head_dim)
    assert blocks.bks[0].shape == (5, 8, slab_heads, cfg.head_dim)
    row = 2 * planes * model_heads * cfg.head_dim * 4
    assert sum(a.nbytes for a in pool.ks + pool.vs) == 3 * 32 * row
    assert sum(a.nbytes for a in blocks.bks + blocks.bvs) == 5 * 8 * row
    # the staging rows the engine prefills into are the model's own cache
    staged = model.init_cache(1, 32)
    assert [c[0].shape for c in staged] \
        == [(1, 32, slab_heads, cfg.head_dim)] * slabs
