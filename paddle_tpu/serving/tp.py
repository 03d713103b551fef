"""Tensor-parallel serving: the mesh, the sharded layouts, the fused
compute-collective decode program.

The serving engine (serving/engine.py) becomes multi-chip by sharding
its WHOLE device plane over a 1-D mesh whose axis is the models' ``mp``
(model-parallel) axis:

  * **KV slot slabs** (kv_pool.KVPool) and the radix **block slab**
    (kv_pool.BlockPool) partition on the kv-head axis — every device
    holds every slot, but only its head group;
  * **weights** partition Megatron-style: QKV / MLP-up column-wise,
    out-proj / MLP-down row-wise, embedding/head on the vocab axis (the
    specs the models already carry for training, reused verbatim for
    GPT; llama's serving layout mirrors ``llama_shard_fn``);
  * the engine's compiled surface ({chunk} + pow2 prefill buckets + ONE
    decode + gather + scatter + sampling) keeps its exact program-set
    size: prefill/gather/scatter/sampling run as GSPMD-partitioned
    programs over the same mesh (sharded operands in, XLA inserts the
    collectives), and the decode step — the latency-critical program —
    runs as ONE explicit shard_map whose TP collectives are fused into
    their adjacent dots (kernels/collective_matmul.py): the entry
    all-gather rides the QKV / MLP-up matmul, the exit reduce-scatter
    rides the out-proj / MLP-down matmul, and the residual stream stays
    slot-sharded between them so norms run local.  With
    ``pallas_block=True`` (the engine's ``tp_fused_block`` path, ISSUE
    12) the same program's layer bodies run the SHARDED Pallas decode
    block instead (kernels/decode_block_tp.py: the rings lowered into
    the Pallas grid, KV append in-kernel on the local slab shard).  See
    docs/serving.md "Tensor-parallel serving".

Per-device decode dataflow (one layer; B slots, tp devices)::

    x [B/tp, D] --norm--> allgather_matmul --> qkv [B, (H+2KH)/tp * dh]
      --rotary/append (local slab shard)--> decode attention (local
      heads) --> matmul_reduce_scatter(out-proj) --> [B/tp, D] +residual
      --norm--> allgather_matmul(MLP up) --> act -->
      matmul_reduce_scatter(MLP down) --> [B/tp, D] +residual

Logits leave the shard_map vocab-sharded (the final allgather_matmul
contracts hidden against the local vocab columns); sampling runs on the
sharded logits under GSPMD inside the same jitted decode program, so the
argmax/top-k reductions over vocab are partitioned too.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["TP_AXIS", "build_serving_mesh", "serving_param_specs",
           "shard_model_params", "sharded_zeros", "tp_decode_supported",
           "build_tp_decode_program", "build_tp_verify_program"]

# graftprog entry-point marker (see tools/analysis/compile_surface.py):
# the TP decode/verify program factories root their shard_map compile
# units on the static manifest.  Read by the AST analysis only; zero
# runtime effect.
__compile_surface_roots__ = ("build_tp_decode_program",
                             "build_tp_verify_program")

# the serving TP axis IS the models' model-parallel axis: the
# Column/RowParallelLinear layers annotate their weights over "mp"
# (distributed/meta_parallel/mp_layers.py), so naming the serving mesh
# the same way lets training specs and activation constraints bind
# unchanged under the serving mesh
TP_AXIS = "mp"

# slot slabs [num_slots, max_seq, kv_heads, head_dim] and block slabs
# [num_blocks, block_len, kv_heads, head_dim] both partition on the
# kv-head axis — axis 2 in either layout
KV_SLAB_SPEC = P(None, None, "mp", None)


def build_serving_mesh(tp: int, devices=None) -> Mesh:
    """A 1-D tensor-parallel mesh over ``tp`` devices (the first ``tp``
    of ``jax.devices()`` by default — on the CPU tier this is the
    XLA_FLAGS virtual-device mesh the MULTICHIP dryruns use)."""
    if tp < 1:
        raise ValueError(f"tensor_parallel must be >= 1, got {tp}")
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < tp:
        raise ValueError(
            f"tensor_parallel={tp} needs {tp} devices but only "
            f"{len(devices)} are visible — on CPU set "
            f"--xla_force_host_platform_device_count (XLA_FLAGS)")
    return Mesh(np.array(devices[:tp]), ("mp",))


def serving_refusal(model) -> Optional[str]:
    """Why ``model`` cannot be served over a tensor-parallel mesh at
    all, or None.  The slot and block slabs partition on their head
    axis (``KV_SLAB_SPEC``); a cache that keeps several planes on that
    axis (``cfg.cache_planes_per_slab``, models/ouro.py) would be split
    by plane, not by head, and its model has no Megatron layout here.
    A model may also refuse for itself (``serving_refusals()``:
    models/jamba.py's recurrent state has no layout over the mesh)."""
    own = getattr(model, "serving_refusals", dict)().get("tensor_parallel")
    if own is not None:
        return own
    per_slab = getattr(model.cfg, "cache_planes_per_slab", None) or 1
    if per_slab > 1:
        return (f"the model's cache slab holds {per_slab} KV planes on "
                f"its head axis, which the kv-head sharding of the "
                f"serving mesh would split by plane (no tensor-parallel "
                f"layout for a looped cache)")
    return None


# --------------------------------------------------------------- layouts
def serving_param_specs(model) -> Dict[str, P]:
    """Dotted-name -> PartitionSpec for the engine's GSPMD programs
    (prefill chunks, staging init, block gather/scatter, sampling).

    Models that already carry TP training specs (GPT's parallel layers
    annotate over ``mp`` via set_param_spec) reuse them verbatim; plain
    models (llama) get the Megatron serving layout by leaf name —
    q/k/v/gate/up column-parallel, o/down row-parallel, embedding and
    lm_head vocab-parallel (embedding ROW-sharded so the fused decode
    bundle and the GSPMD table are one layout)."""
    from ..distributed.sharding_utils import get_param_specs
    specs = get_param_specs(model)
    if any(tuple(s) for s in specs.values()):
        return specs
    col = {"q_proj", "k_proj", "v_proj", "gate_proj", "up_proj", "lm_head"}
    row = {"o_proj", "down_proj"}
    out = {}
    for name in specs:
        parts = name.split(".")
        parent = parts[-2] if len(parts) >= 2 else ""
        if parent in col:
            out[name] = P(None, "mp")
        elif parent in row:
            out[name] = P("mp", None)
        elif parent == "embed_tokens":
            out[name] = P("mp", None)
        else:
            out[name] = P()
    return out


def _spec_fits(shape, spec: P, mesh: Mesh) -> bool:
    for dim, ax in zip(shape, tuple(spec)):
        if ax is None:
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if dim % size:
            return False
    return True


def shard_model_params(model, mesh: Mesh) -> None:
    """Lay the model's parameters out over the serving mesh IN PLACE
    (each param with its serving spec; non-divisible dims fall back to
    replicated).  The engine's jitted programs close over these arrays,
    so every program compiles against the sharded layout.  Layout goes
    through ``sharding_utils.put_global`` — the multi-controller-safe
    ingest — so a multi-host pod slice lays out the same way as a
    single-host mesh."""
    from ..distributed.sharding_utils import put_global
    specs = serving_param_specs(model)
    for lname, sub in model.named_sublayers(include_self=True):
        for pname, p in list(sub._parameters.items()):
            if p is None:
                continue
            key = f"{lname}.{pname}" if lname else pname
            spec = specs.get(key, P())
            if not _spec_fits(p.shape, spec, mesh):
                spec = P()
            sub._parameters[pname] = put_global(
                p, NamedSharding(mesh, spec))


# one compiled zero-filler per (mesh, shape, dtype): pool construction
# and every quarantine rebuild reuse the same program, so slab creation
# is not a recompile treadmill
_ZEROS_CACHE: Dict[tuple, object] = {}


def sharded_zeros(mesh: Mesh, shape, dtype):
    """A builder for kv-head-sharded slabs ([rows, len, kv_heads,
    head_dim]) that are BORN sharded: a jitted zero-fill with
    ``out_shardings`` places each device's shard directly, so the full
    slab never materializes on one device — at pod scale it may not
    fit one, which is the point of sharding it.

    (An eager ``make_array_from_callback`` variant was tried and
    reverted: on the jaxlib-0.4 pin its per-shard host buffers
    nondeterministically crash the cyclic-GC pass conftest already
    documents — the compiled form has never shown it.)"""
    shape, dt = tuple(shape), jnp.dtype(dtype)
    key = (mesh, shape, dt.name)
    fn = _ZEROS_CACHE.get(key)
    if fn is None:
        ns = NamedSharding(mesh, KV_SLAB_SPEC)
        fn = jax.jit(functools.partial(jnp.zeros, shape, dt),
                     out_shardings=ns)
        _ZEROS_CACHE[key] = fn
    return fn


def replicated(x, mesh: Mesh):
    from ..distributed.sharding_utils import put_global
    return put_global(x, NamedSharding(mesh, P()))


# ------------------------------------------------- fused decode program
def tp_decode_supported(model, tp: int,
                        num_slots: int) -> Tuple[bool, Optional[str]]:
    """Static legality of the fused compute-collective decode program
    for ``model`` at this engine shape.  Returns ``(ok, reason)``."""
    if tp == 1:
        return False, "tensor_parallel is 1 (single chip needs no " \
                      "collectives)"
    if not hasattr(model, "tp_decode_weights") \
            or not hasattr(model, "tp_decode_supported"):
        return False, "model has no tp_decode_weights"
    if num_slots % tp:
        return False, (f"num_slots {num_slots} not divisible by "
                       f"tensor_parallel {tp} (the residual stream "
                       f"slot-shards between the fused collectives)")
    return model.tp_decode_supported(tp)


# per-leaf PartitionSpecs of the fused-decode weight bundle (the models'
# tp_decode_weights arranges the globals so an equal contiguous split
# over the mesh axis IS the per-device block)
_BUNDLE_SPECS = {
    "wte": P("mp", None),       # vocab-sharded rows (masked lookup+psum)
    "wpe": P(),                 # learned positions: tiny, replicated
    "head": P(None, "mp"),      # vocab column shard (None when tied)
    "nfw": P(), "nfb": P(),
    "n1w": P(), "n1b": P(), "n2w": P(), "n2b": P(),
    "wqkv": P(None, "mp"), "bqkv": P("mp"),
    "wo": P("mp", None), "bo": P(),
    "wup": P(None, "mp"), "bup": P("mp"),
    "wdown": P("mp", None), "bdown": P(),
}


def _bundle_specs(weights):
    def spec_of(d):
        return {k: (None if v is None
                    else [spec_of(b) for b in v] if k == "blocks"
                    else _BUNDLE_SPECS[k])
                for k, v in d.items()}
    return spec_of(weights)


def _norm(x, w, b, kind: str, eps: float):
    from ..nn import functional as F
    if kind == "rms":
        return F.rms_norm(x, w, None, eps)
    return F.layer_norm(x, (x.shape[-1],), w, b, eps)


def _tp_layer(x_s, pk, pv, seq_pos, blk, arch, rope, axis, tp, overlap,
              s: int = 1):
    """One transformer layer of the per-device decode body: entry
    all-gather fused into the QKV / MLP-up dots, exit reduce-scatter
    fused into the out-proj / MLP-down dots, attention local to this
    device's head group against its slab shard.

    ``s`` is the per-slot token width — 1 for the decode program, the
    ``spec_k+1`` verify window for the speculative verify program.  Rows
    stay flat ``[slots*s, features]`` (slot-major) through the fused
    collective dots and fold back to ``[slots, s, ...]`` only around
    attention, whose ragged visibility comes from ``cache_lens(pos, s)``
    — query t of a slot's window sees keys up to ``pos + t``."""
    from ..kernels.collective_matmul import (allgather_matmul,
                                             matmul_reduce_scatter)
    from ..kernels.decode_attention import append_and_attend
    from ..nn import functional as F
    dh = arch["head_dim"]
    h_l = arch["heads"] // tp
    kh_l = arch["kv_heads"] // tp
    # ---- attention: norm (local rows) -> fused all-gather/QKV dot
    h1 = _norm(x_s, blk["n1w"], blk["n1b"], arch["norm"], arch["eps"])
    qkv = allgather_matmul(h1, blk["wqkv"], axis, tp, overlap=overlap)
    if blk["bqkv"] is not None:
        qkv = qkv + blk["bqkv"]
    rows = qkv.shape[0]
    b = rows // s
    q = qkv[:, :h_l * dh].reshape(b, s, h_l, dh)
    k = qkv[:, h_l * dh:(h_l + kh_l) * dh].reshape(b, s, kh_l, dh)
    v = qkv[:, (h_l + kh_l) * dh:].reshape(b, s, kh_l, dh)
    if rope is not None:
        from ..models.llama import apply_rotary_pos_emb
        cos, sin = rope
        q = apply_rotary_pos_emb(q, cos, sin)
        k = apply_rotary_pos_emb(k, cos, sin)
    # GQA inside the kernel: this device's kv heads, never repeated
    attn, k_buf, v_buf = append_and_attend(q, k, v, pk, pv, seq_pos)
    attn = attn.reshape(rows, h_l * dh)
    # ---- exit: out-proj dot with the reduce-scatter riding it
    o = matmul_reduce_scatter(attn, blk["wo"], axis, tp, overlap=overlap)
    if blk["bo"] is not None:
        o = o + blk["bo"]
    x_s = x_s + o
    # ---- MLP: same entry/exit fusion pattern
    h2 = _norm(x_s, blk["n2w"], blk["n2b"], arch["norm"], arch["eps"])
    up = allgather_matmul(h2, blk["wup"], axis, tp, overlap=overlap)
    if blk["bup"] is not None:
        up = up + blk["bup"]
    if arch["act"] == "swiglu":
        f_l = up.shape[1] // 2
        act = F.silu(up[:, :f_l]) * up[:, f_l:]
    else:
        act = F.gelu(up, approximate=True)
    d = matmul_reduce_scatter(act, blk["wdown"], axis, tp, overlap=overlap)
    if blk["bdown"] is not None:
        d = d + blk["bdown"]
    return x_s + d, k_buf, v_buf


def _tp_decode_body(weights, ks, vs, seq_pos, last_tok, *, arch, tp,
                    axis, overlap, pallas_plan=None):
    """Per-device body of the ONE fused decode program: embed (masked
    vocab-shard lookup + psum) -> slot-shard the residual stream ->
    layers (fused collectives) -> final norm -> logits against the local
    vocab columns (left vocab-sharded for the GSPMD sampling tail).

    With ``pallas_plan`` the layer bodies run as the SHARDED Pallas
    decode-block kernels (kernels/decode_block_tp.py — the entry/exit
    rings lowered into the Pallas grid, attention + in-kernel append on
    the local slab shard); the embed / final-norm / logits legs are
    shared code either way, so the two paths cannot drift outside the
    layer seam."""
    from ..kernels.collective_matmul import allgather_matmul
    idx = jax.lax.axis_index(axis)
    b = last_tok.shape[0]
    b_l = b // tp
    wte_l = weights["wte"]                       # [V/tp, D] local rows
    v_l = wte_l.shape[0]
    loc = last_tok.astype(jnp.int32) - idx * v_l
    ok = (loc >= 0) & (loc < v_l)
    emb = jnp.take(wte_l, jnp.clip(loc, 0, v_l - 1), axis=0)
    emb = jnp.where(ok[:, None], emb, jnp.zeros((), emb.dtype))
    x = jax.lax.psum(emb, axis)                  # [B, D] replicated
    if weights["wpe"] is not None:
        x = x + jnp.take(weights["wpe"], seq_pos, axis=0)
    rope, rope_full = None, None
    if arch["rope"]:
        from ..models.llama import _rope_tables
        if pallas_plan is not None:
            # full-width tables (halves duplicated) at each slot's
            # position — the kernel applies rotary in matrix form,
            # exactly like the models' tp=1 fused_decode_step
            cos, sin = _rope_tables(seq_pos, arch["head_dim"],
                                    arch["rope_theta"], jnp.float32)
            rope_full = (jnp.concatenate([cos, cos], axis=-1),
                         jnp.concatenate([sin, sin], axis=-1))
        else:
            cos, sin = _rope_tables(seq_pos[:, None], arch["head_dim"],
                                    arch["rope_theta"], x.dtype)
            rope = (cos, sin)
    # slot-shard the residual stream: this device's row chunk
    x_s = jax.lax.dynamic_slice_in_dim(x, idx * b_l, b_l, axis=0)
    new_ks, new_vs = [], []
    for blk, pk, pv in zip(weights["blocks"], ks, vs):
        if pallas_plan is not None:
            from ..kernels.decode_block_tp import tp_fused_block_layer
            x_s, kb, vb = tp_fused_block_layer(
                x_s, pk, pv, seq_pos, blk, arch, rope_full, axis, tp,
                pallas_plan)
        else:
            x_s, kb, vb = _tp_layer(x_s, pk, pv, seq_pos, blk, arch,
                                    rope, axis, tp, overlap)
        new_ks.append(kb)
        new_vs.append(vb)
    xf = _norm(x_s, weights["nfw"], weights["nfb"], arch["norm"],
               arch["eps"])
    head_l = weights["head"] if weights["head"] is not None else wte_l.T
    logits = allgather_matmul(xf, head_l, axis, tp, overlap=overlap)
    return logits[:, None, :], new_ks, new_vs, seq_pos + 1


def build_tp_decode_program(model, mesh: Mesh, tp: int, *,
                            overlap: bool = True,
                            pallas_block: bool = False,
                            batch: Optional[int] = None,
                            max_seq: Optional[int] = None):
    """Build the engine's fused compute-collective decode program.
    Returns ``(fn, weights)``: ``fn(weights, ks, vs, seq_pos, last_tok)
    -> (logits, new_ks, new_vs, new_pos)`` with ``logits [num_slots, 1,
    vocab]`` vocab-sharded over the mesh.  NOT jitted — the engine wraps
    it together with its sampling tail in the single compiled decode
    step, so the program-set pin (ONE decode) is unchanged.

    ``pallas_block=True`` builds the ``tp_fused_block`` variant: the
    layer bodies run the sharded Pallas decode-block kernels
    (kernels/decode_block_tp.py) with the entry/exit collectives riding
    the tile dots and the KV append landing in-kernel on the local slab
    shard; ``batch``/``max_seq`` (the engine's num_slots / pool
    max_seq) size the per-shard VMEM plan, which raises if illegal —
    callers are contracted to gate on
    ``decode_block.resolve_fused_decode(tp=...)`` first.

    The weight bundle is laid out here once (device_put per
    ``_BUNDLE_SPECS``) and RETURNED, never captured: the jitted caller
    passes it as an operand, because an array a jitted function closes
    over is compiled into the program as a constant — gathered to the
    host and copied whole into every program, which un-shards it."""
    from ..distributed._jax_compat import shard_map
    from ..distributed.sharding_utils import put_global
    arch, weights = model.tp_decode_weights(tp)
    pallas_plan = None
    if pallas_block:
        from ..kernels.decode_block import plan_decode_block
        gated = arch["act"] == "swiglu"
        blk0 = weights["blocks"][0]
        ffn = blk0["wup"].shape[1] // (2 if gated else 1)
        pallas_plan, why = plan_decode_block(
            max_seq=max_seq, hidden=arch["hidden"], heads=arch["heads"],
            kv_heads=arch["kv_heads"], head_dim=arch["head_dim"],
            ffn=ffn, batch=batch,
            itemsize=jnp.dtype(blk0["wqkv"].dtype).itemsize,
            gated=gated, tp=tp)
        if pallas_plan is None:
            raise ValueError(
                f"build_tp_decode_program(pallas_block=True): no VMEM "
                f"tiling fits ({why}) — gate on resolve_fused_decode "
                f"before requesting the sharded Pallas block")
    specs = _bundle_specs(weights)
    weights = jax.tree.map(
        lambda w, s: None if w is None
        else put_global(w, NamedSharding(mesh, s)),
        weights, specs, is_leaf=lambda x: x is None)
    num_layers = len(weights["blocks"])
    body = functools.partial(_tp_decode_body, arch=arch, tp=tp,
                             axis=TP_AXIS, overlap=overlap,
                             pallas_plan=pallas_plan)
    slab = [KV_SLAB_SPEC] * num_layers

    def program(weights, ks, vs, seq_pos, last_tok):
        return shard_map(
            body, mesh=mesh,
            in_specs=(specs, slab, slab, P(), P()),
            out_specs=(P(None, None, "mp"), slab, slab, P()),
            check_vma=False)(weights, ks, vs, seq_pos, last_tok)

    return program, weights


def _tp_verify_body(weights, ks, vs, seq_pos, ids, *, arch, tp, axis,
                    overlap, width):
    """Per-device body of the ONE fused verify program — the decode
    body at token width ``width`` (= spec_k+1): the ``[B, width]``
    draft windows flatten slot-major to ``[B*width]`` rows so the same
    fused compute-collective dots carry them, each slot's window sits
    at its OWN ``seq_pos`` (embedding offsets, rope, and the ragged
    ``cache_lens`` attention all take per-row position vectors), and
    the layer seam is the SAME ``_tp_layer`` the decode program
    compiles — the two paths cannot drift."""
    from ..kernels.collective_matmul import allgather_matmul
    idx = jax.lax.axis_index(axis)
    b, s = ids.shape
    b_l = b // tp
    flat = ids.reshape(b * s).astype(jnp.int32)
    wte_l = weights["wte"]                       # [V/tp, D] local rows
    v_l = wte_l.shape[0]
    loc = flat - idx * v_l
    ok = (loc >= 0) & (loc < v_l)
    emb = jnp.take(wte_l, jnp.clip(loc, 0, v_l - 1), axis=0)
    emb = jnp.where(ok[:, None], emb, jnp.zeros((), emb.dtype))
    x = jax.lax.psum(emb, axis)                  # [B*s, D] replicated
    pos2d = seq_pos[:, None] + jnp.arange(s)     # [B, s] per-row offsets
    if weights["wpe"] is not None:
        x = x + jnp.take(weights["wpe"], pos2d.reshape(b * s), axis=0)
    rope = None
    if arch["rope"]:
        from ..models.llama import _rope_tables
        cos, sin = _rope_tables(pos2d, arch["head_dim"],
                                arch["rope_theta"], x.dtype)
        rope = (cos, sin)
    # slot-shard the residual stream: this device's slot-major row chunk
    x_s = jax.lax.dynamic_slice_in_dim(x, idx * b_l * s, b_l * s, axis=0)
    new_ks, new_vs = [], []
    for blk, pk, pv in zip(weights["blocks"], ks, vs):
        x_s, kb, vb = _tp_layer(x_s, pk, pv, seq_pos, blk, arch, rope,
                                axis, tp, overlap, s=s)
        new_ks.append(kb)
        new_vs.append(vb)
    xf = _norm(x_s, weights["nfw"], weights["nfb"], arch["norm"],
               arch["eps"])
    head_l = weights["head"] if weights["head"] is not None else wte_l.T
    logits = allgather_matmul(xf, head_l, axis, tp, overlap=overlap)
    return (logits.reshape(b, s, logits.shape[-1]),
            new_ks, new_vs, seq_pos + s)


def build_tp_verify_program(model, mesh: Mesh, tp: int, *, width: int,
                            overlap: bool = True):
    """Build the fused verify program of the speculative-decoding path.
    Returns ``(fn, weights)`` like ``build_tp_decode_program``:
    ``fn(weights, ks, vs, seq_pos, ids) -> (logits, new_ks, new_vs,
    new_pos)`` with ``ids [num_slots, width]`` (each slot's last committed token
    followed by its zero-padded draft window) and ``logits [num_slots,
    width, vocab]`` vocab-sharded over the mesh.  NOT jitted — the
    engine wraps it with its matched-sampling acceptance tail in the
    single compiled verify step, so the program-set pin (ONE verify)
    holds the same way decode's does.

    Same shard_map family as ``build_tp_decode_program`` — identical
    bundle layout, identical in/out specs modulo the width axis, the
    layer bodies ARE ``_tp_layer`` — just at token width ``width``.
    There is no ``pallas_block`` variant: the Pallas decode block
    (kernels/decode_block_tp.py) is a single-token kernel, so the
    ``tp_fused_block`` engine path verifies through THIS program and
    keeps the Pallas block for its decode steps."""
    from ..distributed._jax_compat import shard_map
    from ..distributed.sharding_utils import put_global
    if width < 2:
        raise ValueError(f"verify width must be >= 2 (spec_k >= 1), "
                         f"got {width}")
    arch, weights = model.tp_decode_weights(tp)
    specs = _bundle_specs(weights)
    weights = jax.tree.map(
        lambda w, s: None if w is None
        else put_global(w, NamedSharding(mesh, s)),
        weights, specs, is_leaf=lambda x: x is None)
    num_layers = len(weights["blocks"])
    body = functools.partial(_tp_verify_body, arch=arch, tp=tp,
                             axis=TP_AXIS, overlap=overlap, width=width)
    slab = [KV_SLAB_SPEC] * num_layers

    def program(weights, ks, vs, seq_pos, ids):
        return shard_map(
            body, mesh=mesh,
            in_specs=(specs, slab, slab, P(), P()),
            out_specs=(P(None, None, "mp"), slab, slab, P()),
            check_vma=False)(weights, ks, vs, seq_pos, ids)

    return program, weights
