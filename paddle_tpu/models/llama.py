"""Llama family — the semi-auto-parallel flagship (BASELINE config #4).

Reference model surface: the semi-auto Llama used by
test/auto_parallel/hybrid_strategy/ (semi-auto Llama-2 tests, SURVEY.md §4)
and PaddleNLP's LlamaForCausalLM: RMSNorm, rotary position embeddings,
grouped-query attention, SwiGLU MLP, no biases, untied lm_head.

TPU-native design: the model is written as plain Layers (no hand-rolled
parallel layers) and parallelised the semi-auto way —
``llama_shard_fn(mesh)`` places weights via dist.shard_tensor and GSPMD
partitions the jitted step (SURVEY.md §3.4; the reference path
dist.shard_tensor -> DistTensor -> SPMD rules + reshard is all inside XLA
here).  For the hand-written hybrid path, GPT (models/gpt.py) is the
flagship; Llama is the auto-parallel one, mirroring how the reference
splits its two baselines.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers.common import Linear, Embedding, Dropout
from ..nn.layers.container import LayerList
from ..nn.layers.norm import RMSNorm

__all__ = ["LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaDecoderLayer",
           "LlamaModel", "LlamaForCausalLM", "llama_shard_fn", "llama_tiny",
           "llama_7b"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None      # None -> MHA; < num_heads -> GQA
    max_seq_len: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dropout: float = 0.0
    dtype: str = "float32"
    # False | True (full jax.checkpoint) | a
    # jax.checkpoint_policies name (shared remat_wrap knob)
    remat: "bool | str" = False

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def num_params(self) -> int:
        h, l, v = self.hidden_size, self.num_layers, self.vocab_size
        kvh = self.kv_heads * self.head_dim
        attn = h * h + 2 * h * kvh + h * h          # q, k, v, o
        mlp = 3 * h * self.intermediate_size        # gate, up, down
        norms = 2 * h
        return 2 * v * h + l * (attn + mlp + norms) + h


def _rope_tables(positions, head_dim: int, theta: float, dtype):
    """cos/sin tables [*, head_dim/2] for the given positions."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [..., d/2]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rotary_pos_emb(x, cos, sin):
    """x [b, s, heads, d]; cos/sin [s, d/2] (shared positions) or
    [b, s, d/2] (per-row positions — ragged continuous batching).  Llama
    pairing: (x1, x2) = halves (reference fused_rope neox-style)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class LlamaAttention(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = Linear(h, cfg.num_heads * d, bias_attr=False)
        self.k_proj = Linear(h, cfg.kv_heads * d, bias_attr=False)
        self.v_proj = Linear(h, cfg.kv_heads * d, bias_attr=False)
        self.o_proj = Linear(cfg.num_heads * d, h, bias_attr=False)

    def forward(self, x, cos, sin, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_proj(x).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = self.k_proj(x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
        v = self.v_proj(x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
        q = apply_rotary_pos_emb(q, cos, sin)
        k = apply_rotary_pos_emb(k, cos, sin)
        new_cache = None
        if cache is not None:
            # routed decode attention (see gpt.py _attn), which appends
            # the chunk to the cache on its way: seq_lens = pos + s with
            # the causal tail IS the per-query chunked-prefill mask,
            # with no [*, s, S_max] mask materialization.  pos may be a
            # scalar (dense batch: it broadcasts) or a [b] vector of
            # per-row offsets (ragged continuous batching: each row
            # keeps its own context length) — models/kv_cache.py.
            # GQA happens inside the kernels: the cache is never
            # repeated up to the query heads
            from ..kernels.decode_attention import append_and_attend
            pk, pv, pos = cache
            out, k, v = append_and_attend(q, k, v, pk, pv, pos)
            new_cache = (k, v, pos + s)
        else:
            # GQA: repeat kv heads up to q heads (XLA turns this into a
            # broadcast inside the attention einsum — no real copy)
            rep = cfg.num_heads // k.shape[2]
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 training=self.training)
        out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        return self.o_proj(out), new_cache


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = Linear(h, m, bias_attr=False)
        self.up_proj = Linear(h, m, bias_attr=False)
        self.down_proj = Linear(m, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        self.drop = Dropout(cfg.dropout)

    def forward(self, x, cos, sin, cache=None):
        # a part's scope holds its branch's residual add: XLA makes the
        # add the root of the last projection's fusion, and a fusion is
        # known by its root (obs/parts.py)
        y = self.input_layernorm(x)
        with jax.named_scope("attention"):
            a, new_cache = self.self_attn(y, cos, sin, cache)
            x = x + self.drop(a)
        y = self.post_attention_layernorm(x)
        with jax.named_scope("mlp"):
            x = x + self.drop(self.mlp(y))
        if cache is not None:
            return x, new_cache
        return x

    def fused_decode_step(self, x, cos_full, sin_full, cache):
        """One decode token through the fused decode-block kernel pair
        (kernels/decode_block.py): RMSNorm -> QKV (+rotary) -> in-kernel
        KV append -> GQA streaming attention -> o_proj -> SwiGLU MLP.
        ``cos_full``/``sin_full`` are [B, head_dim] full-width rotary
        tables (halves duplicated) at each row's position; the KV slabs
        in ``cache`` update in place via kernel aliasing."""
        from ..kernels.decode_block import decode_block_layer
        cfg = self.cfg
        pk, pv, pos = cache
        at, mlp = self.self_attn, self.mlp
        y, k2, v2 = decode_block_layer(
            x, pk, pv, pos, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            norm="rms", eps1=cfg.rms_norm_eps, eps2=cfg.rms_norm_eps,
            norm1_w=self.input_layernorm.weight, norm1_b=None,
            wq=at.q_proj.weight, wk=at.k_proj.weight, wv=at.v_proj.weight,
            bq=None, bkv=None, bv=None,
            wo=at.o_proj.weight, bo=None,
            norm2_w=self.post_attention_layernorm.weight, norm2_b=None,
            w1=mlp.up_proj.weight, b1=None,
            w2=mlp.down_proj.weight, b2=None,
            w_gate=mlp.gate_proj.weight,
            rope_cos=cos_full, rope_sin=sin_full)
        return y, (k2, v2, pos + 1)


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(cfg)
                                 for _ in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None, position_offset: int = 0):
        cfg = self.cfg
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        # offset + static arange: position_offset may be traced (generate);
        # a [b] offset vector gives per-row positions (ragged batching)
        with jax.named_scope("attention"):
            pos = jnp.asarray(position_offset)[..., None] + jnp.arange(s)
            cos, sin = _rope_tables(pos, cfg.head_dim, cfg.rope_theta,
                                    x.dtype)
        new_caches = []
        for i, layer in enumerate(self.layers):
            if caches is None:
                from ..distributed.recompute import remat_wrap
                x = remat_wrap(lambda x_, lyr=layer: lyr(x_, cos, sin),
                               cfg.remat)(x)
            else:
                x, c = layer(x, cos, sin, caches[i])
                new_caches.append(c)
        x = self.norm(x)
        return x if caches is None else (x, new_caches)


class LlamaForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.llama = LlamaModel(cfg)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias_attr=False)

    def _head(self, hidden):
        with jax.named_scope("head"):
            return self.lm_head(hidden)

    def forward(self, input_ids):
        return self._head(self.llama(input_ids))

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            tok = jnp.take_along_axis(logp, labels[..., None],
                                      axis=-1)[..., 0]
            return -jnp.mean(tok)

    def chunked_loss(self, input_ids, labels, n_chunks: int = 8):
        """Causal LM loss without materializing [b, s, V] logits (the
        chunked-vocab head+CE — see GPTForCausalLM.chunked_loss).  The
        untied lm_head's [h, V] weight enters transposed; XLA fuses the
        transpose into the chunk matmuls."""
        from ..nn.functional import chunked_softmax_cross_entropy
        hidden = self.llama(input_ids)
        b, s, h = hidden.shape
        per_tok = chunked_softmax_cross_entropy(
            hidden.reshape(b * s, h), self.lm_head.weight.T,
            labels.reshape(-1), n_chunks=n_chunks)
        return jnp.mean(per_tok)

    # ---- incremental decode -------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None):
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        return [(jnp.zeros((batch, max_len, cfg.kv_heads, cfg.head_dim), dt),
                 jnp.zeros((batch, max_len, cfg.kv_heads, cfg.head_dim), dt),
                 jnp.asarray(0, jnp.int32)) for _ in range(cfg.num_layers)]

    def decode_step(self, input_ids, caches, position: int):
        hidden, new_caches = self.llama(input_ids, caches,
                                        position_offset=position)
        return self._head(hidden), new_caches

    def fused_decode_supported(self, batch: int = 1,
                               kv_len: Optional[int] = None,
                               tp: int = 1):
        """Static legality of the fused decode-block path (GQA aware);
        ``tp > 1`` checks the sharded variant's per-shard plan
        (kernels/decode_block_tp.py).  Returns ``(ok, reason)``."""
        from ..kernels.decode_block import fusion_legal
        cfg = self.cfg
        if cfg.dropout and self.training:
            return False, "dropout active (training mode)"
        return fusion_legal(
            max_seq=kv_len or cfg.max_seq_len, hidden=cfg.hidden_size,
            heads=cfg.num_heads, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim, ffn=cfg.intermediate_size, batch=batch,
            dtype=cfg.dtype, gated=True, tp=tp)

    def fused_decode_step(self, input_ids, caches, position):
        """``decode_step`` through the fused decode-block kernels —
        shared embed/final-norm/head legs, fused layer bodies, rotary
        tables computed once at each row's position (full-width, halves
        duplicated: the kernel applies rotary in matrix form)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self.llama.embed_tokens(input_ids)
        with jax.named_scope("attention"):
            pos = jnp.asarray(position, jnp.int32)
            if pos.ndim == 0:
                pos = jnp.full((x.shape[0],), pos, jnp.int32)
            cos, sin = _rope_tables(pos, cfg.head_dim, cfg.rope_theta,
                                    jnp.float32)             # [B, d/2]
            cos_full = jnp.concatenate([cos, cos], axis=-1)
            sin_full = jnp.concatenate([sin, sin], axis=-1)
        new_caches = []
        for layer, cache in zip(self.llama.layers, caches):
            x, c = layer.fused_decode_step(x, cos_full, sin_full, cache)
            new_caches.append(c)
        x = self.llama.norm(x)
        return self._head(x), new_caches

    def generate(self, input_ids, max_new_tokens: int, **kw):
        """Single-scan autoregressive decoding (models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, **kw)

    # ---- tensor-parallel serving (serving/tp.py) ----------------------
    def tp_decode_supported(self, tp: int):
        """Static legality of the fused compute-collective TP decode
        program at degree ``tp`` (GQA aware: the kv-head axis must tile
        the mesh too, since the KV slot slabs partition on it).
        Returns ``(ok, reason)``."""
        cfg = self.cfg
        for what, n in (("num_heads", cfg.num_heads),
                        ("kv_heads", cfg.kv_heads),
                        ("intermediate_size", cfg.intermediate_size),
                        ("vocab_size", cfg.vocab_size)):
            if n % tp:
                return False, (f"{what} {n} not divisible by "
                               f"tensor_parallel {tp}")
        return True, None

    def tp_decode_weights(self, tp: int):
        """``(arch, weights)`` for the serving TP decode program
        (serving/tp.py): q/k/v column shards re-arranged per device as
        ``[q_d | k_d | v_d]`` head-group blocks (one fused entry
        matmul), gate/up as ``[gate_d | up_d]`` (one fused MLP-up
        matmul); o/down stay row-parallel, embedding/lm_head
        vocab-parallel."""
        cfg = self.cfg
        dh = cfg.head_dim
        arch = {"norm": "rms", "eps": cfg.rms_norm_eps, "act": "swiglu",
                "rope": True, "rope_theta": cfg.rope_theta,
                "heads": cfg.num_heads, "kv_heads": cfg.kv_heads,
                "head_dim": dh, "hidden": cfg.hidden_size,
                "vocab": cfg.vocab_size}
        qs, kvs, fs = ((cfg.num_heads // tp) * dh,
                       (cfg.kv_heads // tp) * dh,
                       cfg.intermediate_size // tp)
        blocks = []
        for layer in self.llama.layers:
            at, mlp = layer.self_attn, layer.mlp
            parts, mparts = [], []
            for d in range(tp):
                parts += [at.q_proj.weight[:, d * qs:(d + 1) * qs],
                          at.k_proj.weight[:, d * kvs:(d + 1) * kvs],
                          at.v_proj.weight[:, d * kvs:(d + 1) * kvs]]
                mparts += [mlp.gate_proj.weight[:, d * fs:(d + 1) * fs],
                           mlp.up_proj.weight[:, d * fs:(d + 1) * fs]]
            blocks.append({
                "n1w": layer.input_layernorm.weight, "n1b": None,
                "wqkv": jnp.concatenate(parts, axis=1), "bqkv": None,
                "wo": at.o_proj.weight, "bo": None,
                "n2w": layer.post_attention_layernorm.weight,
                "n2b": None,
                "wup": jnp.concatenate(mparts, axis=1), "bup": None,
                "wdown": mlp.down_proj.weight, "bdown": None})
        return arch, {
            "wte": self.llama.embed_tokens.weight, "wpe": None,
            "head": self.lm_head.weight,
            "nfw": self.llama.norm.weight, "nfb": None,
            "blocks": blocks}


# ---------------------------------------------------------------------------
# semi-auto sharding plan (reference: the hybrid_strategy llama tests call
# dist.shard_tensor on q/k/v/o and gate/up/down with [Replicate, Shard(...)])
# ---------------------------------------------------------------------------

def llama_shard_fn(mesh, dp_axis: str = "dp", mp_axis: str = "mp"):
    """Build a shard_fn for dist.shard_layer: Megatron-style TP placement
    over ``mp_axis``; everything else replicated (dp comes from the batch).
    """
    from ..distributed.auto_parallel import shard_tensor, Shard, Replicate

    mp_dim = mesh.dim_names.index(mp_axis)

    def place(sub, pname, tensor_dim):
        p = sub._parameters.get(pname)
        if p is None:
            return
        pl = [Replicate()] * mesh.ndim
        pl[mp_dim] = Shard(tensor_dim)
        sub._parameters[pname] = shard_tensor(p, mesh, pl)

    def shard_fn(name, sub, m):
        leaf = name.rsplit(".", 1)[-1]
        if leaf in ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"):
            place(sub, "weight", 1)   # column parallel: [h, out/mp]
        elif leaf in ("o_proj", "down_proj"):
            place(sub, "weight", 0)   # row parallel: [in/mp, h]
        elif leaf == "embed_tokens":
            place(sub, "weight", 1)   # hidden-sharded embedding
        elif leaf == "lm_head":
            place(sub, "weight", 1)   # vocab-parallel logits

    return shard_fn


def llama_tiny(**kw) -> LlamaConfig:
    return LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=176,
                       num_layers=2, num_heads=4, num_kv_heads=2,
                       max_seq_len=128, **kw)


def llama_7b(**kw) -> LlamaConfig:
    # Llama-2-7B: 32 layers, 4096 hidden, 11008 ffn, 32 heads, MHA
    return LlamaConfig(vocab_size=32000, hidden_size=4096,
                       intermediate_size=11008, num_layers=32, num_heads=32,
                       max_seq_len=4096, dtype="bfloat16", **kw)
