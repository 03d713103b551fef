"""DeepSeek-V3-shaped decoders (models/deepseek_v3.py): latent attention
over ONE cached row a position and dropless experts beside a shared one,
on the CPU at a small size (hidden 64, 4 heads, ranks 40 / 32, nope 24 !=
rope 8 != v 16, a dense first layer and two expert layers of 8 experts
top-2, float32) against the plain reference of
``benchmarks/builders/deepseek_v3.py``, which shares no code with the
model: the full forward; absorbed against expanded attention; chunked
and padded prefill then ragged cached decode through ``ServingEngine``
with other slots live, parked and reused, LOGITS against logits;
routers that fill one expert and that leave 200 empty, nothing dropped;
parked and padded rows that touch no expert.  The comparison has to
fail on the wrong builds listed under FAULTS; the pool has to hold one
row kind for this model and K and V for the other four families."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from benchmarks.builders import deepseek_v3 as builder
from benchmarks.lib.reference import F32_LOGIT_TOL
from paddle_tpu.core.flags import flags
from paddle_tpu.distributed import moe_dropless
from paddle_tpu.distributed.moe_dropless import DroplessMoE
from paddle_tpu.models import (DeepseekV3Config, DeepseekV3ForCausalLM,
                               GPTForCausalLM, JambaForCausalLM,
                               LlamaForCausalLM, OuroForCausalLM,
                               deepseek_v3_tiny, gpt_tiny, jamba_tiny,
                               llama_tiny, ouro_tiny)
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.models.llama import apply_rotary_pos_emb
from paddle_tpu.nn.functional_call import state
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_pool import KVPool, cache_geometry, cache_row

VOCAB = 128


def file_config(cfg: DeepseekV3Config) -> dict:
    """The configuration-file form the reference reads."""
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "qk_head_dim": cfg.qk_head_dim, "v_head_dim": cfg.v_head_dim,
            "n_routed_experts": cfg.n_routed_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_shared_experts": cfg.n_shared_experts,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "torch_dtype": cfg.dtype,
            "max_position_embeddings": cfg.max_seq_len}


def make_model(seed=0, **cfg_kw):
    """A seeded model whose norm weights and choice bias are NOT their
    initial ones and zeros, and whose matrices are large enough for
    every branch to matter."""
    paddle_tpu.seed(seed)
    model = DeepseekV3ForCausalLM(deepseek_v3_tiny(
        **{"initializer_range": 0.15, **cfg_kw}))
    model.eval()
    params, _ = state(model)
    key = jax.random.key(seed + 100)
    moved = {}
    for i, (name, p) in enumerate(sorted(params.items())):
        if "layernorm" in name or name.endswith(
                ("norm.weight", "e_score_correction_bias")):
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


def test_the_builder_makes_the_programs_config():
    cfg = deepseek_v3_tiny()
    assert builder.model_config(file_config(cfg)) == cfg
    with pytest.raises(ValueError, match="only one group is implemented"):
        builder.model_config({**file_config(cfg), "n_group": 8,
                              "topk_group": 4})
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        builder.model_config({**file_config(cfg),
                              "num_nextn_predict_layers": 1})


def test_published_sizes():
    cfg = DeepseekV3Config()
    # ISSUE 34: 529,530,880 + 70,391,808 + 39 x 1,239,554,304 + 2,048
    assert cfg.num_params() == 529_530_880 + 70_391_808 \
        + 39 * 1_239_554_304 + 2_048
    d5 = DeepseekV3Config(num_layers=5)
    assert d5.num_params() == 5_558_141_952
    # the 576-wide latent row is held in whole 128-lane tiles
    assert (d5.latent_width, d5.cache_row_width, d5.cache_row_kinds,
            d5.kv_heads) == (576, 640, 1, 1)
    assert d5.num_expert_layers == 4
    model = make_model()
    assert sum(p.size for p in state(model)[0].values()) \
        == model.cfg.num_params()


def test_the_initializer_departs_in_the_routed_down_projections_alone():
    """``routed_out_range`` draws the routed experts' ``down_proj`` and
    nothing else; the cell's configuration file sets it to 0.02 /
    sqrt(2 x 40) beside the family's 0.02 (PERF.md section 6, PR 34)."""
    import json
    import os
    paddle_tpu.seed(0)
    model = DeepseekV3ForCausalLM(deepseek_v3_tiny(
        hidden_size=128, initializer_range=0.02, routed_out_range=0.005))
    for name, p in state(model)[0].items():
        if p.ndim < 2:
            continue
        want = 0.005 if name.endswith("mlp.down_proj") else 0.02
        assert float(jnp.std(p)) == pytest.approx(want, rel=0.08), name
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "joyai-llm-flash-d5.json")) as f:
        body = json.load(f)
    assert body["initializer"] == {"range": 0.02, "routed_out": 0.00223607}
    cfg = builder.model_config(body)
    assert (cfg.initializer_range, cfg.routed_out) == (0.02, 0.00223607)
    assert cfg.routed_out == pytest.approx(0.02 / math.sqrt(2 * 40), 1e-6)
    assert DeepseekV3Config().routed_out == 0.02


@pytest.mark.parametrize("cached", [False, True])
def test_absorbed_attention_is_the_expanded_one(ids, cached):
    """The program's ABSORBED attention against the EXPANDED one (K and
    V rebuilt per head, which is what the plain reference computes), on
    the attention branch alone and on the logits: over a chunk that
    attends to itself and over a chunk appended to rows already held."""
    model = make_model()
    x = jnp.asarray(ids)
    if not cached:
        got = model(x)
        # one layer's attention branch against the reference's
        layer = model.model.layers[1]
        u = jnp.asarray(np.random.default_rng(7).normal(
            size=(2, 37, model.cfg.hidden_size)), jnp.float32)
        cos, sin = ds._rope_tables(jnp.arange(37), 8,
                                   model.cfg.rope_theta, jnp.float32)
        params = state(model)[0]
        want = builder._attention(
            file_config(model.cfg),
            lambda n: params["model.layers.1.self_attn." + n], u)
        assert rel_err(layer.self_attn(u, cos, sin)[0],
                       np.asarray(want)) <= 1e-5
    else:
        caches = model.init_cache(2, 64)
        _, caches, _ = model.decode_step(x[:, :20], caches, 0)
        got = model.decode_step(x[:, 20:], caches, 20)[0]
    assert rel_err(got, reference(model, ids)[:, 20 * cached:]) \
        <= F32_LOGIT_TOL


def test_the_decode_kernel_is_the_absorbed_attention(ids, monkeypatch):
    """``kernels/latent_attention.py`` (interpreted), ragged positions
    with a parked row at 0 and a full one at the slab's last row,
    against the XLA absorbed form on the same cache, and the slab it
    leaves against ``append_rows``."""
    model = make_model()
    x = jnp.asarray(np.concatenate([ids, ids[:1, ::-1]]))   # 3 rows
    caches = model.init_cache(3, 48)
    _, caches, _ = model.decode_step(x[:, :36], caches, 0)
    pos = jnp.asarray([0, 17, 47], jnp.int32)
    tok = x[:, 36:37]
    route, why = model.attention_route(caches[0][0].shape, jnp.float32)
    assert (route, why) == ("latent_in_place", None)
    got, kept, _ = model.decode_step(tok, caches, pos)
    with monkeypatch.context() as m:
        m.setattr(flags, "pallas_routing", "never")
        assert model.attention_route(caches[0][0].shape, jnp.float32)[0] \
            == "xla_dense"
        want, held, _ = model.decode_step(tok, caches, pos)
    assert rel_err(got, np.asarray(want)) <= 1e-5
    # the first layer's slab to the bit, the later ones as their
    # inputs agree
    np.testing.assert_array_equal(np.asarray(kept[0][0]),
                                  np.asarray(held[0][0]))
    for a, b in zip(kept, held):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                                   atol=1e-5)
    from paddle_tpu.kernels.latent_attention import latent_attention_route
    assert latent_attention_route((4, 40, 1, 128), jnp.float32) \
        == ("xla_dense", "max_seq 40 is not a multiple of 16")


@pytest.mark.parametrize("offset,width", [
    (0, 64), (488, 64), (960, 64), (0, 8), (509, 8), (1016, 8)],
    ids=["full_first", "full_mid", "full_last", "narrow_first",
         "narrow_mid", "narrow_last"])
def test_the_chunk_kernel_is_the_absorbed_attention(offset, width,
                                                     monkeypatch):
    """``kernels/latent_attention.py``'s chunk kernel (interpreted) in a
    cached chunk's attention branch, against the XLA absorbed form with
    ``_seen`` on the same rows: a staging of 1,024 rows (two row tiles)
    whose rows held before the chunk are random and whose rows at and
    past ``offset + width`` hold NaN.  The kernel's output stays finite
    (nothing past the chunk's last row reaches it) where the XLA form's
    does not (``p = 0`` times NaN), at chunks of two query tiles and of
    part of one, at the staging's start, across the tiles' boundary and
    at its end."""
    from paddle_tpu.kernels.latent_attention import latent_chunk_route
    model = make_model()
    cfg = model.cfg
    attn = model.model.layers[1].self_attn
    rng = np.random.default_rng(offset + width)
    u = jnp.asarray(rng.normal(size=(1, width, cfg.hidden_size)),
                    jnp.float32)
    cos, sin = ds._rope_tables(offset + jnp.arange(width),
                               cfg.qk_rope_head_dim, cfg.rope_theta,
                               jnp.float32)
    rows = rng.normal(size=(1, 1024, 1, cfg.cache_row_width))
    rows[:, offset:] = np.nan
    staging = jnp.asarray(rows, jnp.float32)
    clean = staging.at[:, offset + width:].set(0.0)
    assert latent_chunk_route(staging.shape, width, jnp.float32) \
        == ("latent_chunk", None)
    got, (kept, _, _) = attn(u, cos, sin, (staging, None, offset))
    with monkeypatch.context() as m:
        m.setattr(flags, "pallas_routing", "never")
        assert latent_chunk_route(staging.shape, width, jnp.float32) \
            == ("xla_dense", "FLAGS_pallas_routing=never")
        want, (held, _, _) = attn(u, cos, sin, (clean, None, offset))
        dense = attn(u, cos, sin, (staging, None, offset))[0]
    assert np.isfinite(np.asarray(got)).all()
    assert rel_err(got, np.asarray(want)) <= 1e-5
    end = offset + width
    np.testing.assert_array_equal(np.asarray(kept[:, :end]),
                                  np.asarray(held[:, :end]))
    assert np.isfinite(np.asarray(dense)).all() == (end == 1024)
    assert latent_chunk_route((1, 1024, 1, 128), 2048, jnp.float32) \
        == ("xla_dense", "chunk width 2048 is past max_seq 1024")


def test_the_rotary_pairing_is_the_interleaved_one():
    """De-interleaving then rotating halves is the rotation of the pairs
    ``(2j, 2j+1)`` in another column order, so ``q . k`` is the
    published score."""
    rs = np.random.default_rng(3)
    q, k = (jnp.asarray(rs.normal(size=(1, 5, 2, 8)), jnp.float32)
            for _ in range(2))
    pos = jnp.arange(5)
    cos, sin = ds._rope_tables(pos, 8, 32e6, jnp.float32)
    ours = jnp.einsum(
        "bshd,bshd->bsh",
        apply_rotary_pos_emb(ds._deinterleave(q), cos, sin),
        apply_rotary_pos_emb(ds._deinterleave(k), cos, sin))
    theirs = jnp.einsum("bshd,bshd->bsh",
                        builder._rotate_pairs(q, pos, 32e6),
                        builder._rotate_pairs(k, pos, 32e6))
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- the expert layer

def moe_layer(experts=8, top_k=2, hidden=64, size=48, seed=0):
    paddle_tpu.seed(seed)
    layer = DroplessMoE(hidden, size, experts, top_k, n_shared=1,
                        routed_scale=2.5, init_std=0.15)
    layer.eval()
    return layer


def dense_experts(layer, u):
    """The reference's sum over ALL experts on the layer's weights."""
    cfg = {"num_experts_per_tok": layer.top_k, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "n_shared_experts": 1}
    params = {"mlp." + k: v for k, v in state(layer)[0].items()}
    out, w = builder._experts(cfg, params, "mlp.", u)
    return np.asarray(out), np.asarray(w)


def test_every_token_to_one_pair_of_experts_and_nothing_dropped():
    """A router that sends EVERY token to the same two experts (a
    capacity factor would drop most of them): each gets all 96 rows."""
    layer = moe_layer()
    bias = np.full((8,), -5.0, np.float32)
    bias[[2, 6]] = 5.0
    layer.set_state_dict({"e_score_correction_bias": jnp.asarray(bias)})
    u = jnp.asarray(np.random.default_rng(1).normal(size=(96, 64)),
                    jnp.float32)
    out, rows = layer(u)
    assert np.asarray(rows).tolist() == [0, 0, 96, 0, 0, 0, 96, 0]
    want, _ = dense_experts(layer, u)
    assert rel_err(out, want) <= F32_LOGIT_TOL


def test_two_hundred_experts_left_empty_and_nothing_dropped():
    """256 experts top-8, 200 of them never chosen: the groups are
    ragged and mostly empty, every assignment is computed."""
    layer = moe_layer(experts=256, top_k=8)
    bias = np.zeros((256,), np.float32)
    bias[np.random.default_rng(2).permutation(256)[:200]] = -9.0
    layer.set_state_dict({"e_score_correction_bias": jnp.asarray(bias)})
    u = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    out, rows = layer(u)
    rows = np.asarray(rows)
    assert rows.sum() == 40 * 8 and (rows[bias < 0] == 0).all()
    assert np.count_nonzero(rows) <= 56
    want, w = dense_experts(layer, u)
    assert (np.count_nonzero(w, axis=-1) == 8).all()
    assert rel_err(out, want) <= F32_LOGIT_TOL


def test_rows_that_are_not_live_reach_no_expert():
    layer = moe_layer()
    u = jnp.asarray(np.random.default_rng(1).normal(size=(12, 64)),
                    jnp.float32)
    live = jnp.asarray([True] * 3 + [False] * 9)
    out, rows = layer(u, live)
    assert int(np.asarray(rows).sum()) == 3 * 2
    want, _ = dense_experts(layer, u[:3])
    assert rel_err(out[:3], want) <= F32_LOGIT_TOL
    # a row that is not live gets the shared expert alone: finite
    assert bool(jnp.all(jnp.isfinite(out)))


def test_the_kernel_form_is_the_ragged_dot(monkeypatch):
    """``megablox.gmm`` (interpreted) on sorted rows with empty groups
    and rows past the last group, against ``ragged_dot``."""
    rs = np.random.default_rng(0)
    lhs = jnp.asarray(rs.normal(size=(64, 128)), jnp.float32)
    rhs = jnp.asarray(rs.normal(size=(6, 128, 128)), jnp.float32)
    sizes = jnp.asarray([0, 17, 0, 30, 1, 0], jnp.int32)    # 48 of 64
    want = moe_dropless.ragged_form(lhs, rhs, sizes)
    got = moe_dropless.gmm_form(lhs, rhs, sizes, row_tile=16,
                                interpret=True)
    np.testing.assert_allclose(got[:48], want[:48], rtol=2e-5, atol=2e-4)
    assert moe_dropless.grouped_matmul_route(256, 2048, 768, "bfloat16") \
        == ("ragged_dot", "no TPU: the Pallas grouped matmul is a Mosaic "
                          "kernel")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe_dropless.grouped_matmul_route(256, 2048, 768, "bfloat16") \
        == ("gmm", None)
    assert moe_dropless.grouped_matmul_route(4096, 768, 2048, "bfloat16") \
        == ("gmm", None)
    assert moe_dropless.grouped_matmul_route(256, 2048, 48, "bfloat16")[0] \
        == "ragged_dot"


def test_a_bfloat16_router_flips_a_constructed_near_tie():
    """Experts 3 and 5 score 2e-4 apart at the edge of the choice: the
    float32 router tells them apart, one rounded to bfloat16 cannot, and
    the layer's output moves by far more than any tolerance."""
    layer = moe_layer()
    u = jnp.asarray(np.random.default_rng(4).normal(size=(1, 64)),
                    jnp.float32)
    unit = np.asarray(u[0]) / float(jnp.sum(u * u))
    gate = 0.01 * np.random.default_rng(5).normal(size=(64, 8))
    gate[:, 0] = 3.0 * unit
    # expert 3's column holds bfloat16 values; expert 5's is 2e-4 above
    # it, far inside half a bfloat16 step, so it rounds back onto it
    gate[:, 3] = np.asarray(jnp.asarray(2.0 * unit, jnp.bfloat16),
                            np.float32)
    gate[:, 5] = gate[:, 3] * (1 + 2e-4)
    layer.set_state_dict({"gate.weight": jnp.asarray(gate, jnp.float32)})
    idx, _ = layer.route(u)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 5]
    right, _ = layer(u)
    want, _ = dense_experts(layer, u)
    assert rel_err(right, want) <= F32_LOGIT_TOL

    def in_bfloat16(self, u32):
        logits = jnp.dot(u32.astype(jnp.bfloat16),
                         self.gate.weight.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        return moe_dropless.sigmoid_topk_route(
            logits, self.e_score_correction_bias, self.top_k,
            self.routed_scale, self.normalize)

    layer.route = in_bfloat16.__get__(layer)
    idx, _ = layer.route(u)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]
    assert rel_err(layer(u)[0], want) > 50 * F32_LOGIT_TOL


# -------------------------------------------------- through ServingEngine

class Spy:
    """Logits of every ``decode_step`` call the engine's programs make,
    handed to the host by a callback in the program."""

    def __init__(self, model):
        self.calls = []
        inner = model.decode_step

        def spied(input_ids, caches, position, valid=None):
            logits, new, rows = inner(input_ids, caches, position,
                                      valid=valid)
            jax.debug.callback(
                lambda l, p: self.calls.append((np.asarray(l),
                                                np.asarray(p))),
                logits, position)
            return logits, new, rows

        model.decode_step = spied


def serve(model, prompts, new_tokens, late=2, slots=3, **engine_kw):
    """Serve ``prompts`` as one ragged batch, the last ``late`` of them
    submitted two steps after the others (with 3 slots the fourth waits
    for a slot another request frees: a row REUSED while others are
    live, and parked rows ride along until then).  Returns per request
    its tokens, the logits that produced each of them and the prefill's
    logits over the prompt."""
    spy = Spy(model)
    engine_kw.setdefault("enable_prefix_cache", False)
    eng = ServingEngine(model, num_slots=slots, min_bucket=8, max_seq=96,
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


@pytest.mark.parametrize("chunk", [None, 16])
def test_engine_prefill_and_decode_agree_with_the_reference(chunk):
    model = make_model()
    prompts = prompts_of(LENGTHS)
    served = serve(model, prompts, 6, prefill_chunk=chunk)
    assert engine_err(model, served, prompts) <= F32_LOGIT_TOL
    # the engine's tokens are the greedy ones of generate()
    for p, (tokens, _, _) in zip(prompts, served):
        want = np.asarray(model.generate(p[None], max_new_tokens=6))
        assert tokens == want[0, len(p):].tolist()


# ---- the wrong builds: each returns (the faulty model, the model whose
# weights the reference is given)

def _route_variant(monkeypatch, fn):
    monkeypatch.setattr(moe_dropless, "sigmoid_topk_route", fn)
    return make_model(), make_model()


def bias_used_in_the_weights(monkeypatch):
    def route(logits, bias, top_k, scale, normalize=True):
        s = jax.nn.sigmoid(logits) + bias
        w, idx = jax.lax.top_k(s, top_k)
        return idx, scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return _route_variant(monkeypatch, route)


def weights_not_normalized(monkeypatch):
    inner = moe_dropless.sigmoid_topk_route
    return _route_variant(
        monkeypatch, lambda lg, b, k, scale, normalize=True:
        inner(lg, b, k, scale, False))


def routed_scale_left_out(monkeypatch):
    inner = moe_dropless.sigmoid_topk_route
    return _route_variant(
        monkeypatch, lambda lg, b, k, scale, normalize=True:
        inner(lg, b, k, 1.0, normalize))


def rotary_on_the_nope_columns(monkeypatch):
    model = make_model()
    for layer in model.model.layers:
        attn = layer.self_attn
        inner = attn._queries_and_row

        def turned(x, cos, sin, inner=inner):
            qn, qr, row = inner(x, cos, sin)
            head = apply_rotary_pos_emb(qn[..., :8], cos, sin)
            return jnp.concatenate([head, qn[..., 8:]], -1), qr, row
        attn._queries_and_row = turned
    return model, make_model()


def scale_of_the_nope_width(monkeypatch):
    model = make_model()
    for layer in model.model.layers:
        layer.self_attn.softmax_scale = 1.0 / math.sqrt(
            model.cfg.qk_nope_head_dim)
    return model, make_model()


def shared_expert_left_out(monkeypatch):
    model = make_model()
    for layer in model.model.layers:
        if layer.sparse:
            layer.mlp.n_shared = 0
    return model, make_model()


def kv_norm_left_out(monkeypatch):
    model = make_model()
    for layer in model.model.layers:
        layer.self_attn.kv_a_layernorm.forward = lambda x: x
    return model, make_model()


def parked_rows_routed(monkeypatch):
    """Not a fault of the LOGITS (a parked row's experts change no live
    row) but of the count: see the spans' test.  Here: padding routed
    leaves the logits right, so this build must PASS."""
    model = make_model()
    inner = model.decode_step
    model.decode_step = lambda ids, caches, pos, valid=None: \
        inner(ids, caches, pos, valid=None)
    return model, make_model()


FAULTS = [bias_used_in_the_weights, weights_not_normalized,
          routed_scale_left_out, rotary_on_the_nope_columns,
          scale_of_the_nope_width, shared_expert_left_out,
          kv_norm_left_out]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_comparison_fails_on(fault, monkeypatch):
    """The same comparison as above, in chunks, on a model with one
    fault."""
    model, right = fault(monkeypatch)
    prompts = prompts_of(LENGTHS)
    served = serve(model, prompts, 6, prefill_chunk=16)
    assert engine_err(right, served, prompts) > 50 * F32_LOGIT_TOL


def test_routing_the_padding_changes_no_logit(monkeypatch):
    """What ``valid`` buys is bytes, not numbers: a build that routes
    parked and padded rows serves the same logits."""
    model, right = parked_rows_routed(monkeypatch)
    prompts = prompts_of(LENGTHS)
    served = serve(model, prompts, 6, prefill_chunk=16)
    assert engine_err(right, served, prompts) <= F32_LOGIT_TOL


def test_a_slot_freed_and_adopted_again_serves_as_a_fresh_engine():
    """One slot: the second request takes the row the first left and
    must read none of it."""
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


def test_parked_slots_stay_finite_and_unrouted_over_two_thousand_steps(
        monkeypatch):
    """A free slot and one whose request finished ride along in every
    decode step for as long as a run has steps: their rows stay finite,
    they touch no expert, and a request adopted into such a row
    afterwards is served as by a fresh engine.  (Attention by the dense
    XLA form: the interpreted kernel is slow here, and is not what is
    tested.)"""
    from paddle_tpu.obs import Tracer
    monkeypatch.setattr(flags, "pallas_routing", "never")
    model = make_model(max_seq_len=2200)
    long_p, short_p, late_p = prompts_of((12, 9, 17), seed=7)
    eng = ServingEngine(model, num_slots=3, min_bucket=8, max_seq=2200,
                        enable_prefix_cache=False,
                        tracer=Tracer(max_spans=1 << 16))
    try:
        long_id = eng.submit(long_p, max_new_tokens=2050)
        short_id = eng.submit(short_p, max_new_tokens=3)
        eng.run_until_complete(2200)
        assert eng.result(long_id).status == "finished"
        assert len(eng.result(long_id).tokens) == 2050
        assert eng.core._step_index >= 2000
        for slab in eng.core.pool.ks:
            assert bool(jnp.all(jnp.isfinite(slab))), "a parked row"
        alone = [s for s in eng.tracer.spans(lane=0)
                 if s.name == "serving.step"
                 and s.attrs["active_slots"] == 1]
        assert len(alone) >= 2000
        # one live slot of three, top-2, two expert layers
        assert all(0 < s.attrs["experts_touched"] <= 4 for s in alone)
        late = eng.serve_batch([late_p, late_p, late_p], max_new_tokens=5)
    finally:
        eng.close()
    want = np.asarray(model.generate(late_p[None], max_new_tokens=5))
    for out in late:
        assert list(out.tokens) == want[0, len(late_p):].tolist()
    assert eng.result(short_id).status == "finished"


# ------------------------------------------------------------- refusals

def test_the_engine_refuses_what_a_latent_cache_forbids(tmp_path):
    model = make_model()
    with pytest.raises(ValueError, match="hold K and V blocks; a cached "
                                         "position here is ONE latent row"):
        ServingEngine(model, num_slots=2, max_seq=64)   # the cache's default
    with pytest.raises(ValueError, match="tensor_parallel 2: one latent "
                                         "row a position does not "
                                         "partition"):
        ServingEngine(make_model(), num_slots=2, max_seq=64,
                      enable_prefix_cache=False, tensor_parallel=2)
    with pytest.raises(ValueError, match="one row kind has no blocks"):
        from paddle_tpu.serving.kv_pool import BlockPool
        BlockPool.create(model, 4, 16, 64)
    from paddle_tpu.serving.aot import AOTStore, AOTStoreError
    from scripts import aot_build
    assert aot_build.main(["build", str(tmp_path), "--model", "gpt_tiny",
                           "--seed", "0"]) == 0
    store = AOTStore.open(str(tmp_path))
    try:
        with pytest.raises(AOTStoreError, match="K and V slab lists"):
            ServingEngine(make_model(), num_slots=2, max_seq=64,
                          enable_prefix_cache=False, aot_store=store)
    finally:
        store.close()
    eng = ServingEngine(model, num_slots=2, min_bucket=8, max_seq=64,
                        enable_prefix_cache=False, spec_k=3,
                        fused_decode=True)
    try:
        assert not eng.core.spec_on
        assert "live mask into the expert layers" \
            in eng.core.spec_fallback_reason
        assert eng.decode_path == "unfused"
        assert "latent attention and expert layers" \
            in eng.decode_fallback_reason
        # and it serves, one token a step, through the plain program
        prompt = prompts_of((20,))[0]
        out = eng.serve_batch([prompt], max_new_tokens=4)[0]
        want = np.asarray(model.generate(prompt[None], max_new_tokens=4))
        assert list(out.tokens) == want[0, 20:].tolist()
        assert eng.core.trace_counts["verify"] == 0
    finally:
        eng.close()


# ------------------------------------------------------- pools and spans

def _family(name):
    return {"gpt": lambda: GPTForCausalLM(gpt_tiny()),
            "llama": lambda: LlamaForCausalLM(llama_tiny()),
            "ouro": lambda: OuroForCausalLM(ouro_tiny()),
            "jamba": lambda: JambaForCausalLM(jamba_tiny()),
            "deepseek_v3": lambda: DeepseekV3ForCausalLM(
                deepseek_v3_tiny())}[name]()


@pytest.mark.parametrize("family, geometry, row, row_bytes", [
    ("gpt", (2, 2, 4), (2, 16), 2 * 2 * 4 * 16 * 4),
    ("llama", (2, 2, 2), (2, 16), 2 * 2 * 2 * 16 * 4),
    ("ouro", (9, 1, 36), (2, 16), 2 * 36 * 16 * 4),
    ("jamba", (2, 2, 1), (2, 16), 2 * 2 * 1 * 16 * 4),
    # 3 layers of ONE row of 32 + 8 values, in a 128-lane tile
    ("deepseek_v3", (3, 3, 1), (1, 128), 3 * 128 * 4)])
def test_pools_of_five_families(family, geometry, row, row_bytes):
    paddle_tpu.seed(0)
    model = _family(family)
    assert cache_geometry(model.cfg) == geometry
    assert cache_row(model.cfg) == row
    pool = KVPool.create(model, num_slots=3, max_seq=32)
    assert pool.planes == geometry[0] and len(pool.ks) == geometry[1]
    assert pool.row_kinds == row[0] and pool.row_bytes == row_bytes
    assert pool.ks[0].shape == (3, 32, geometry[2], row[1])
    if row[0] == 1:
        assert pool.vs == [None] * geometry[1]
    else:
        assert all(v.shape == pool.ks[0].shape for v in pool.vs)
    held = sum(a.nbytes for a in pool.ks) \
        + sum(a.nbytes for a in pool.vs if a is not None)
    assert held == 3 * 32 * row_bytes
    caches = pool.caches()
    assert len(caches) == geometry[1] and caches[0][2] is pool.seq_pos


def test_spans_counts_gauges_and_the_load():
    """``experts_touched`` / ``expert_rows_max`` on the step span riding
    the token readback, ``experts_touched`` on the request lane's
    chunks, the two gauges, the routes on the ``decode_block`` event and
    the accessors; the load on the device counts every live row once a
    layer."""
    model = make_model()
    eng = ServingEngine(model, num_slots=8, min_bucket=8, max_seq=96,
                        prefill_chunk=16, enable_prefix_cache=False)
    try:
        prompt, = prompts_of((37,))
        out, = eng.serve_batch([prompt], max_new_tokens=5)
        snap = eng.registry.snapshot()
        assert snap["serving.cache.row_bytes"] == 3 * 128 * 4
        assert snap["serving.moe.experts"] == 2 * 8
        assert snap["serving.kv.planes"] == 3
        steps = [s for s in eng.tracer.spans(lane=0)
                 if s.name == "serving.step"]
        decoding = [s for s in steps if s.attrs["active_slots"]]
        # 1 live slot of 8: at most top-2 x 2 expert layers, the seven
        # parked rows touch nothing
        assert decoding and all(
            2 <= s.attrs["experts_touched"] <= 4
            and s.attrs["expert_rows_max"] == 1 for s in decoding)
        assert all(s.attrs["experts_touched"] == 0 for s in steps
                   if not s.attrs["active_slots"])
        chunks = [s for s in eng.tracer.spans()
                  if s.name == "prefill_chunk"]
        assert [(s.attrs["chunk"], s.attrs["width"], s.attrs["tokens"],
                 s.attrs["offset"]) for s in chunks] \
            == [(0, 16, 16, 0), (1, 16, 16, 16), (2, 8, 5, 32)]
        # 96 rows are ONE row tile: each chunk's program read all of it
        assert [s.attrs["attended_rows"] for s in chunks] == [96] * 3
        # the padded chunk's 3 pad tokens reach no expert: at most
        # 5 tokens x top-2 x 2 layers
        assert all(0 < s.attrs["experts_touched"] <= 16 for s in chunks)
        assert chunks[2].attrs["experts_touched"] <= 5 * 2 * 2
        event, = [a for name, _, _, a in eng.tracer.events()
                  if name == "decode_block"]
        assert event["expert_route"] == "prefill=ragged_dot,decode=ragged_dot"
        assert "no TPU" in event["expert_reason"]
        assert event["attention_route"] == "latent_in_place"
        assert event["kv_append"] == "in_kernel"
        assert (event["prefill_attention_route"],
                event["prefill_attention_reason"]) == ("latent_chunk", "")
        assert eng.core.attention_route() == ("latent_in_place", None)
        assert eng.core.prefill_attention_route() == ("latent_chunk", None)
        assert eng.core.expert_route()[0] == event["expert_route"]
        # at the cell's geometry: whole 512-row tiles up to the chunk's
        # last row, every row where the kernel does not run
        bf16 = jnp.bfloat16
        assert [model.attended_rows((1, 4096, 1, 640), off, w, bf16)
                for off, w in ((0, 512), (0, 64), (1024, 512),
                               (2560, 16), (3584, 512))] \
            == [512, 512, 1536, 3072, 4096]
        routing, flags.pallas_routing = flags.pallas_routing, "never"
        try:
            assert model.attended_rows((1, 4096, 1, 640), 0, 512,
                                       bf16) == 4096
        finally:
            flags.pallas_routing = routing
        load = eng.core.expert_load()
        assert load.shape == (2, 8)
        # 37 prompt tokens and 4 decode steps (the first token comes
        # from the prefill), top-2, in each of the 2 expert layers
        assert load.sum(axis=1).tolist() == [(37 + 4) * 2] * 2
    finally:
        eng.close()
    # every other model: blanks and zeros, the same readback
    paddle_tpu.seed(0)
    gpt = GPTForCausalLM(gpt_tiny())
    gpt.eval()
    eng = ServingEngine(gpt, num_slots=2, max_seq=64)
    try:
        eng.serve_batch([np.arange(5)], max_new_tokens=3)
        assert eng.core.expert_load() is None
        assert eng.core.expert_route() == ("", None)
        assert eng.core.prefill_attention_route() == ("", None)
        event, = [a for name, _, _, a in eng.tracer.events()
                  if name == "decode_block"]
        assert event["prefill_attention_route"] == ""
        chunks = [s for s in eng.tracer.spans() if s.name == "prefill_chunk"]
        assert chunks and all("attended_rows" not in s.attrs
                              for s in chunks)
        snap = eng.registry.snapshot()
        assert snap["serving.moe.experts"] == 0
        assert snap["serving.cache.row_bytes"] == 2 * 2 * 4 * 16 * 4
        assert all(s.attrs["experts_touched"] == 0
                   for s in eng.tracer.spans(lane=0)
                   if s.name == "serving.step")
    finally:
        eng.close()
