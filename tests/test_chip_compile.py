"""Every ``pallas_call`` under paddle_tpu/kernels/ is compiled by Mosaic.

The suite runs on the CPU, where the kernels default to ``interpret=True``
and no TPU compiler ever sees them: a kernel Mosaic refuses passes every
other test (the fused decode block did, from PR 7 to PR 20).  libtpu ships
a compile-only client, so this file AOT-compiles each kernel with
``interpret=False`` against a detached v5e topology, at the widths
``chip_smoke.py`` runs on the chip (GPT-3 6.7B: h4096, 32x128 heads, ffn
16384, s2048, bf16; 8 slots).  Compiling is all it can do: numerics are
the interpret-mode tests' and the chip run's.

A missing topology is a failure, not a skip: without it this gate is
silently off.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

B, S, H, DH = 8, 2048, 32, 128          # slots, max_seq, heads, head_dim
D, FFN = H * DH, 4 * H * DH
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")


def _compile(fn, *specs, **jit_kw):
    """Trace, lower for TPU and run XLA:TPU + Mosaic; returns the
    executable's memory analysis."""
    compiled = jax.jit(fn, **jit_kw).trace(*specs).lower(
        lowering_platforms=("tpu",)).compile()
    return compiled.memory_analysis()


def _one(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=BF16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def test_topology_is_v5e(topo):
    assert len(topo.devices) == 4
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("varlen", [False, True])
def test_flash_attention_fwd_bwd(topo, varlen):
    """Forward, dQ and dK/dV kernels at the training shape (the
    segment-masked variants behind flash_attn_unpadded too)."""
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    flash_attention_varlen)
    s = _one(topo)
    qkv = [s((1, S, H, DH))] * 3

    if varlen:
        def loss(q, k, v, seg):
            return flash_attention_varlen(
                q, k, v, seg, seg, causal=True,
                interpret=False).astype(jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv,
                 s((1, S), jnp.int32))
    else:
        def loss(q, k, v):
            return flash_attention(
                q, k, v, causal=True,
                interpret=False).astype(jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv)


@pytest.mark.parametrize("seq,head_dim,dtype", [
    (4096, 128, BF16),              # mistral-7b.train-4k's attention
    (4096, 128, jnp.float32), (4096, 64, BF16), (4096, 64, jnp.float32),
    (4096, 256, BF16), (4096, 256, jnp.float32), (2048, 128, BF16),
    (8192, 128, BF16)])
def test_flash_attention_planned_tiles(topo, seq, head_dim, dtype):
    """Mosaic takes the tiles ``flash_attention_plan`` picks from the
    shape and the dtype, under the scoped-VMEM limit the kernels state,
    for all three kernels: the plan's budget arithmetic against the
    chip's compiler."""
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    plan = fa.flash_attention_plan(seq, seq, head_dim, dtype, causal=True)
    assert all(p["vmem_bytes"] <= fa.VMEM_BUDGET for p in plan.values())
    s = _one(topo)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             *[s((2, seq, 4, head_dim), dtype)] * 3)


@pytest.mark.parametrize("slots,sq,rows,kv_heads,slab_heads,route", [
    (16, 1, S, H, H, "slab_in_place"),          # gpt3-6.7b.serve-chat
    (8, 1, 512, 16, 192 * 16, "slab_in_place"),  # one of Ouro-2.6B's planes
    (8, 1, S, 8, 8, "slab_in_place"),           # GQA 32:8, cache not repeated
    (8, 4, S, H, H, "slab_in_place"),           # a speculative verify window
    (1, 64, S, H, H, "head_major_copy"),        # a 64-token prefill chunk
], ids=["serve_chat", "ouro_plane", "gqa", "verify", "prefill_chunk"])
def test_decode_attention(topo, slots, sq, rows, kv_heads, slab_heads,
                          route):
    """Decode against the slot slabs where they lie (a plane by its
    TRACED first head), and a prefill chunk on the copying kernel: the
    kernel each shape takes on the chip, compiled by Mosaic, alone and
    as ``append_and_attend`` runs it.  The appending form writes the
    fresh rows into the donated slabs itself: both are aliased and
    ``temp`` holds no plane."""
    import importlib
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    s = _one(topo)
    heads = H if kv_heads != 16 else 16
    q, slab = s((slots, sq, heads, DH)), s((slots, rows, slab_heads, DH))
    assert da.pallas_attention_route(q.shape, slab.shape, BF16,
                                     kv_heads)[0] == route

    def attn(q, k, v, lens, head0):
        return da.decode_attention(q, k, v, lens, interpret=False,
                                   head0=head0, kv_heads=kv_heads)

    mem = _compile(attn, q, slab, slab, s((slots,), jnp.int32),
                   s((), jnp.int32))
    plane = slots * rows * kv_heads * DH * 2
    if route == "slab_in_place":
        assert mem.temp_size_in_bytes < plane, (
            f"temp {mem.temp_size_in_bytes} B holds a copy of a plane "
            f"({plane} B)")

    def append(q, kn, vn, k, v, pos, head0):
        return da.append_and_attend(q, kn, vn, k, v, pos, head0=head0,
                                    kv_heads=kv_heads, interpret=False)

    fresh = s((slots, sq, kv_heads, DH))
    mem = _compile(append, q, fresh, fresh, slab, slab,
                   s((slots,), jnp.int32), s((), jnp.int32),
                   donate_argnums=(3, 4))
    if route == "slab_in_place":
        assert mem.temp_size_in_bytes < plane, mem.temp_size_in_bytes
        assert mem.alias_size_in_bytes >= 2 * plane * (slab_heads
                                                       // kv_heads)


def test_looped_decode_step_moves_no_plane(topo, monkeypatch):
    """Ouro-2.6B's whole decode step (48 layers x 4 passes, 192 planes
    in ONE slab per K and V, 8 slots x 512 rows: the benchmark's cell)
    with the chip's routes: the scans append to the 3.2 GB slabs in
    place and the kernel windows each plane out of them, so ``temp``
    holds no plane (16.8 MB), let alone a slab (PR 28 read 3.2 GB from
    one wrong form of the append, 1.2 MB from the right one)."""
    from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
    from paddle_tpu.nn.functional_call import bind_state, state
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = OuroConfig(max_seq_len=512, dtype="bfloat16")
    made = []

    def make():
        made.append(OuroForCausalLM(cfg).to(dtype=cfg.dtype))
        return state(made[0])

    s = _one(topo)
    params, buffers = jax.tree.map(lambda x: s(x.shape, x.dtype),
                                   jax.eval_shape(make))
    model = made[0]
    slots, rows = 8, 512
    slab = s((slots, rows, cfg.num_cache_layers * cfg.kv_heads,
              cfg.head_dim))

    def decode(params, k, v, pos, tok):
        with bind_state(model, params, buffers):
            logits, caches = model.decode_step(tok[:, None],
                                               [(k, v, pos)], pos)
        return jnp.argmax(logits[:, 0], -1), caches[0][0], caches[0][1]

    mem = _compile(decode, params, slab, slab, s((slots,), jnp.int32),
                   s((slots,), jnp.int32), donate_argnums=(1, 2))
    plane = slots * rows * cfg.kv_heads * cfg.head_dim * 2
    assert mem.temp_size_in_bytes < plane // 2, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 2 * plane * cfg.num_cache_layers


@pytest.mark.parametrize("width", [512, 16])
def test_selective_scan(topo, width):
    """The state-space recurrence over one prefill chunk at Jamba2-3B's
    widths (d_inner 5120, d_state 16): ``[N, 8, 128]`` state tiles in
    vregs, B and C as SMEM scalars.  ``temp`` holds nothing of ``[width,
    16, 5120]``."""
    from paddle_tpu.kernels import selective_scan as ss
    s = _one(topo)
    f32 = jnp.float32
    d, n = 5120, 16
    assert ss.scan_route(width, d) == ("pallas_chunk", None)
    seq, bc = s((1, width, d), f32), s((1, width, n), f32)
    mem = _compile(functools.partial(ss.selective_scan, interpret=False),
                   seq, seq, s((n, d), f32), bc, bc, s((1, n, d), f32))
    assert mem.temp_size_in_bytes < width * d * 4, mem.temp_size_in_bytes


def test_hybrid_prefill_chunk_and_decode_step(topo, monkeypatch):
    """Jamba2-3B's whole prefill program at the cell's chunk (512 tokens
    into a 4096-row staging, the recurrent state carried and ``valid``
    handed in) and its decode step over 16 slots, with the chip's
    routes: 26 scan kernels, two attention layers of 20 queries a KV
    head (the copying kernel, in sub-blocks of 64 tokens for a chunk:
    512 x 20 query rows at once are 27 MB of its VMEM).  KV rows and
    all 52 state arrays are updated in place."""
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM
    from paddle_tpu.nn.functional_call import bind_state, state
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = JambaConfig(max_seq_len=4096, dtype="bfloat16")
    made = []

    def make():
        made.append(JambaForCausalLM(cfg).to(dtype=cfg.dtype))
        return state(made[0])

    s = _one(topo)
    params, buffers = jax.tree.map(lambda x: s(x.shape, x.dtype),
                                   jax.eval_shape(make))
    model = made[0]
    assert model.recurrence_route(512) == ("pallas_chunk", None)

    def carried(batch):
        return jax.tree.map(lambda z: s((batch,) + z.shape, z.dtype),
                            model.recurrent_state_spec())

    def step(params, ks, vs, ids, pos, valid, st):
        caches = [(k, v, pos) for k, v in zip(ks, vs)]
        with bind_state(model, params, buffers):
            logits, caches, st = model.decode_step(ids, caches, pos,
                                                   state=st, valid=valid)
        return (logits[:, -1], [c[0] for c in caches],
                [c[1] for c in caches], st)

    i32 = jnp.int32
    per_slot = 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    slab = s((1, 4096, 1, 128))
    mem = _compile(step, params, [slab, slab], [slab, slab],
                   s((1, 512), i32), s((), i32), s((), i32), carried(1),
                   donate_argnums=(1, 2, 6))
    assert mem.alias_size_in_bytes >= 4 * 4096 * 128 * 2 + per_slot
    assert mem.temp_size_in_bytes < 512 * 65536 * 4 + (64 << 20)
    slab = s((16, 4096, 1, 128))
    mem = _compile(lambda p, ks, vs, ids, pos, st: step(
        p, ks, vs, ids, pos, None, st), params, [slab, slab], [slab, slab],
        s((16, 1), i32), s((16,), i32), carried(16),
        donate_argnums=(1, 2, 5))
    assert mem.alias_size_in_bytes >= 16 * (4 * 4096 * 128 * 2 + per_slot)
    assert mem.temp_size_in_bytes < 16 * per_slot // 2


@pytest.mark.parametrize("rows, tile", [(256, 16), (4096, 128)])
def test_grouped_matmul_over_the_experts(topo, rows, tile):
    """``megablox.gmm`` at JoyAI-LLM-Flash's expert widths with a whole
    ``[2048, 768]`` / ``[768, 2048]`` expert matrix a grid step: a decode
    step's 32 slots x 8 assignments in row tiles of 16, a 512-token
    chunk's 4096 in tiles of 128."""
    from paddle_tpu.distributed import moe_dropless as M
    s = _one(topo)
    assert M._row_tile(rows) == tile
    for k, n in ((2048, 768), (768, 2048)):
        mem = _compile(functools.partial(M.gmm_form, interpret=False),
                       s((rows, k)), s((256, k, n)),
                       s((256,), jnp.int32))
        assert mem.temp_size_in_bytes < (8 << 20), mem.temp_size_in_bytes


def test_latent_decode_attention(topo):
    """The latent-row decode kernel over 32 slots x 4096 rows of 640
    lanes (the 576-wide row in whole tiles), 32 query heads: the slab is
    updated in place."""
    from paddle_tpu.kernels.latent_attention import latent_decode_attention
    s = _one(topo)
    slab = s((32, 4096, 1, 640))
    mem = _compile(functools.partial(latent_decode_attention, lora=512,
                                     scale=192 ** -0.5, interpret=False),
                   s((32, 32, 640)), s((32, 1, 1, 640)), slab,
                   s((32,), jnp.int32), donate_argnums=(2,))
    assert mem.alias_size_in_bytes == 32 * 4096 * 640 * 2
    assert mem.temp_size_in_bytes < (1 << 20), mem.temp_size_in_bytes


@pytest.mark.parametrize("width", [512, 16])
def test_latent_chunk_attention(topo, width):
    """The latent-row chunk kernel over a request's 4096-row staging of
    640 lanes, 32 query heads, at the cell's chunk and at a narrow last
    chunk: Mosaic takes it under the scoped VMEM it states, and the
    program holds nothing but its output."""
    from paddle_tpu.kernels.latent_attention import latent_chunk_attention
    s = _one(topo)
    mem = _compile(functools.partial(latent_chunk_attention, lora=512,
                                     scale=192 ** -0.5, interpret=False),
                   s((1, width, 32, 640)), s((1, 4096, 1, 640)),
                   s((1,), jnp.int32))
    assert mem.output_size_in_bytes == width * 32 * 512 * 4
    assert mem.temp_size_in_bytes < (1 << 20), mem.temp_size_in_bytes


def test_latent_expert_decode_step_prefill_chunk_and_reference(
        topo, monkeypatch, capsys):
    """JoyAI-LLM-Flash at the cell's depth (5 layers, every expert,
    5.56 B parameters): its decode step over 32 slots, its prefill
    program at the cell's chunk into a 4096-row staging, both with the
    chip's routes (12 grouped-matmul kernels, 5 latent-attention kernels
    in the decode step), ``alias`` covering the cache; the initializer
    as ``lib/build.py`` runs it; and the plain float32 reference at the
    cell's longest sequence beside the 11.1 GB of weights.  ``temp`` of
    each is printed."""
    import json
    import os
    from benchmarks.builders import deepseek_v3 as builder
    from benchmarks.lib import reference
    from paddle_tpu.framework.random import rng_context
    from paddle_tpu.nn.functional_call import bind_state, state
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "joyai-llm-flash-d5.json")) as f:
        cfg_file = json.load(f)
    cfg = builder.model_config(cfg_file, 4096)
    made = []

    def make(key=None):
        if key is None:
            made.append(builder.model_class()(cfg).to(dtype=cfg.dtype))
        else:
            with rng_context(key):
                made.append(builder.model_class()(cfg).to(dtype=cfg.dtype))
        return state(made[-1])

    s = _one(topo)
    params, buffers = jax.tree.map(lambda x: s(x.shape, x.dtype),
                                   jax.eval_shape(make))
    model = made[0]
    assert model.expert_route(32) == model.expert_route(512) == ("gmm", None)
    i32, gb = jnp.int32, 1e9

    def step(params, ks, ids, pos, valid):
        caches = [(k, None, pos) for k in ks]
        with bind_state(model, params, buffers):
            logits, caches, rows = model.decode_step(ids, caches, pos,
                                                     valid=valid)
        return logits[:, -1], [c[0] for c in caches], rows

    temps = {}
    slabs = [s((32, 4096, 1, 640))] * 5
    text = jax.jit(step, donate_argnums=(1,)).trace(
        params, slabs, s((32, 1), i32), s((32,), i32),
        s((32,), i32)).lower(lowering_platforms=("tpu",)).compile()
    mem = text.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * 32 * 4096 * 640 * 2
    hlo = text.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 17
    temps["decode"] = mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes < 0.1 * gb
    mem = _compile(step, params, [s((1, 4096, 1, 640))] * 5,
                   s((1, 512), i32), s((), i32), s((), i32),
                   donate_argnums=(1,))
    assert mem.alias_size_in_bytes >= 5 * 4096 * 640 * 2
    temps["prefill_512"] = mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes < 1.0 * gb
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=SingleDeviceSharding(topo.devices[0]))
    mem = _compile(make, key)
    # small arrays are padded out to their tiles
    assert 0 <= mem.output_size_in_bytes - 2 * 5_558_141_952 < (1 << 20)
    temps["initializer"] = mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes < 0.5 * gb

    def forward(p, ids):
        with jax.default_matmul_precision("highest"):
            return builder.reference_forward(cfg_file, reference._f32(p), ids)

    mem = _compile(forward, params, s((1, 3840), i32))
    temps["reference_3840"] = mem.temp_size_in_bytes
    # beside 11.12 GB of weights on a 16 GiB chip
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 4.5 * gb
    with capsys.disabled():
        print("\njoyai-llm-flash-d5 temp bytes:", json.dumps(temps))


@pytest.mark.parametrize("kind", ["rms", "layer"])
def test_fused_norm_fwd_bwd(topo, kind):
    from paddle_tpu.kernels.fused_norm import (fused_layer_norm_pallas,
                                               fused_rms_norm_pallas)
    s = _one(topo)
    if kind == "rms":
        def loss(x, w):
            return fused_rms_norm_pallas(
                x, w, interpret=False).astype(jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1)), s((8192, D)), s((D,)))
    else:
        def loss(x, w, b):
            return fused_layer_norm_pallas(
                x, w, b, interpret=False).astype(jnp.float32).sum()
        _compile(jax.grad(loss, argnums=(0, 1, 2)), s((8192, D)),
                 s((D,)), s((D,)))


def test_fused_adamw(topo):
    """One out-projection's worth of parameters, standalone (the
    tighter scoped-VMEM context, SKILL.md r4)."""
    from paddle_tpu.kernels.fused_adamw import fused_adamw_update
    s = _one(topo)
    f32 = jnp.float32
    _compile(functools.partial(fused_adamw_update, interpret=False),
             s((D, D)), s((D, D)), s((D, D), f32), s((D, D), f32),
             s((), jnp.int32), s((), f32))


def _layer_specs(s, heads, kv_heads, gated):
    rows = dict(norm1_w=(D,), norm1_b=(D,), wq=(D, heads * DH),
                wk=(D, kv_heads * DH), wv=(D, kv_heads * DH),
                bq=(heads * DH,), bkv=(kv_heads * DH,),
                bv=(kv_heads * DH,), wo=(heads * DH, D), bo=(D,),
                norm2_w=(D,), norm2_b=(D,), w1=(D, FFN), b1=(FFN,),
                w2=(FFN, D), b2=(D,))
    if gated:
        rows["w_gate"] = (D, FFN)
    return {k: s(v) for k, v in rows.items()}


@pytest.mark.parametrize("wiring", ["gpt", "gqa_rope_swiglu"])
def test_decode_block_layer(topo, wiring):
    """The fused decode layer (norm+projection, slab attention,
    out-projection+MLP kernels) at the plan's own tiles, slabs donated:
    the in-kernel append must alias them in place (no temp copy)."""
    from paddle_tpu.kernels.decode_block import decode_block_layer
    s = _one(topo)
    gqa = wiring != "gpt"
    kv_heads = 8 if gqa else H
    weights = _layer_specs(s, H, kv_heads, gated=gqa)
    rope = {"rope_cos": s((B, DH), jnp.float32),
            "rope_sin": s((B, DH), jnp.float32)} if gqa else {}

    def layer(x, k, v, pos, weights, rope):
        return decode_block_layer(
            x, k, v, pos, kv_heads=kv_heads, head_dim=DH,
            norm="rms" if gqa else "layer", eps1=1e-5, eps2=1e-5,
            act="gelu_tanh", interpret=False, **weights, **rope)

    slab = s((B, S, kv_heads, DH))
    mem = _compile(layer, s((B, 1, D)), slab, slab, s((B,), jnp.int32),
                   weights, rope, donate_argnums=(1, 2))
    slab_bytes = B * S * kv_heads * DH * 2
    assert mem.temp_size_in_bytes < slab_bytes, (
        f"temp {mem.temp_size_in_bytes} B holds a slab copy "
        f"({slab_bytes} B): the KV append no longer aliases in place")


def test_decode_block_tp_layer(topo):
    """The sharded fused layer over the four-chip topology: entry and
    exit ring kernels, the per-shard slab attention, and the ppermute
    hops between them, in ONE shard_map program."""
    from paddle_tpu.kernels.decode_block import plan_decode_block
    from paddle_tpu.kernels.decode_block_tp import tp_fused_block_layer
    tp = 4
    mesh = Mesh(np.array(topo.devices[:tp]), ("mp",))

    def ns(shape, spec, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    plan, why = plan_decode_block(
        max_seq=S, hidden=D, heads=H, kv_heads=H, head_dim=DH, ffn=FFN,
        batch=B, itemsize=2, tp=tp)
    assert plan is not None, why
    arch = {"norm": "layer", "eps": 1e-5, "act": "gelu_tanh", "heads": H,
            "kv_heads": H, "head_dim": DH}
    shapes = dict(n1w=(D,), n1b=(D,), wqkv=(D, 3 * D), bqkv=(3 * D,),
                  wo=(D, D), bo=(D,), n2w=(D,), n2b=(D,), wup=(D, FFN),
                  bup=(FFN,), wdown=(FFN, D), bdown=(D,))
    specs = {k: P() for k in shapes}
    specs.update(wqkv=P(None, "mp"), bqkv=P("mp"), wo=P("mp", None),
                 wup=P(None, "mp"), bup=P("mp"), wdown=P("mp", None))
    slab = P(None, None, "mp", None)

    def body(x_s, pk, pv, pos, blk):
        return tp_fused_block_layer(x_s, pk, pv, pos, blk, arch, None,
                                    "mp", tp, plan, interpret=False)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P("mp", None), slab, slab, P(), specs),
        out_specs=(P("mp", None), slab, slab), check_vma=False)
    _compile(fn, ns((B, D), P("mp", None)), ns((B, S, H, DH), slab),
             ns((B, S, H, DH), slab), ns((B,), P(), jnp.int32),
             {k: ns(shapes[k], specs[k]) for k in shapes},
             donate_argnums=(1, 2))


@pytest.mark.parametrize("barrier", [True, False],
                         ids=["barrier", "identity"])
def test_train_step_updates_hold_no_matmul(topo, barrier, monkeypatch):
    """PR 33: with ``Optimizer.update``'s per-leaf gradient barrier no
    fusion of the compiled train step both holds a weight-gradient
    ``convolution`` and writes an updated parameter or optimizer state;
    with the barrier replaced by the identity the compiler fuses them
    again (so this case can see what it guards).  The benchmark's train
    step at small widths, through ``scripts/train_step_fusions.py``."""
    import os
    from benchmarks import run as R
    from scripts import train_step_fusions as T
    files = R.Files(os.path.join(T.ROOT, "BENCHMARK.json"))
    cfg = files.json(files.find("configs/mistral-7b-d2.json"))
    cfg.update(hidden_size=256, intermediate_size=512, vocab_size=1024,
               num_attention_heads=2, num_key_value_heads=1)
    mix = dict(files.json(files.find("traffic/train-4k.json")), seq=512)
    if not barrier:
        monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    rep = T.train_step_report(files.module("builders/llama.py"), cfg, mix,
                              "AdamW", topo.devices[0])
    leaves = rep["gradient_leaves"]
    assert rep["update_fusions"] == leaves == 21
    if barrier:
        assert rep["update_fusions_with_matmul"] == 0, rep["fusions"]
        assert rep["grad_barriers"] == leaves
        assert rep["weight_gradient_fusions"] == 15
    else:
        assert rep["update_fusions_with_matmul"] > 0
        assert rep["grad_barriers"] == 0


def test_every_kernel_module_is_covered():
    """A new ``pallas_call`` site must join this file: the modules that
    hold one are exactly the ones compiled above."""
    import os
    import re
    import paddle_tpu.kernels as kernels
    root = os.path.dirname(kernels.__file__)
    holders = set()
    for name in sorted(os.listdir(root)):
        if name.endswith(".py"):
            with open(os.path.join(root, name)) as fh:
                if re.search(r"\bpl\.pallas_call\(", fh.read()):
                    holders.add(name)
    assert holders == {"flash_attention.py", "decode_attention.py",
                       "fused_norm.py", "fused_adamw.py",
                       "decode_block.py", "decode_block_tp.py",
                       "selective_scan.py", "latent_attention.py"}, holders
