"""GPT — the flagship hybrid-parallel decoder LM (BASELINE config #3).

Reference model surface: the fleet GPT used by
test/collective/fleet/hybrid_parallel* and PaddleNLP's GPT-3 configs —
VocabParallelEmbedding + learned positions, pre-LN blocks with
Column/RowParallelLinear attention+MLP, vocab-parallel loss
(c_softmax_with_cross_entropy), fused_multi_transformer decode path
(paddle/phi/kernels/fusion/gpu — fused_multi_transformer_op.cu).

TPU-native design:
  * weights carry PartitionSpecs (mp for TP; stacked-block leading axis for
    PP) — XLA inserts all collectives;
  * attention routes through F.scaled_dot_product_attention (Pallas flash
    kernel on TPU for long seq);
  * the decode path is a functional KV-cache step (cache in buffers) — the
    fused_multi_transformer equivalent is one jitted decode step whose ops
    XLA fuses; a Pallas fused-block variant lives in paddle_tpu/kernels;
  * ``gpt_train_step_builder`` builds the full dp×mp×pp×sp jitted train
    step used by __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.container import LayerList
from ..nn.layers.norm import LayerNorm
from ..nn.layers.common import Dropout
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    parallel_cross_entropy, _maybe_constraint)

__all__ = ["GPTConfig", "GPTBlock", "GPTModel", "GPTForCausalLM",
           "gpt_tiny", "gpt_small", "gpt3_6_7b"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ffn_mult: int = 4
    dropout: float = 0.0
    attn_dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    tie_embeddings: bool = True
    dtype: str = "float32"
    use_bias: bool = True
    # parallel/runtime knobs
    sp: bool = False          # sequence-parallel activations between blocks
    # jax.checkpoint per block: False | True (full) | a
    # jax.checkpoint_policies name (e.g. "dots_saveable")
    remat: "bool | str" = True
    # context parallelism over the sep mesh axis: None | "ring" | "ulysses"
    # (reference: sep_degree in hybrid_configs; ring attn from PaddleNLP)
    cp: "str | None" = None

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self):
        return self.hidden_size * self.ffn_mult

    def num_params(self) -> int:
        h, l, v = self.hidden_size, self.num_layers, self.vocab_size
        per_block = 4 * h * h + 2 * h * self.ffn_size + \
            (9 * h + 2 * self.ffn_size if self.use_bias else 4 * h)
        emb = v * h + self.max_seq_len * h
        head = 0 if self.tie_embeddings else v * h
        return emb + l * per_block + 2 * h + head


class GPTBlock(Layer):
    """Pre-LN transformer decoder block; shape-preserving (pipeline body)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.ln_1 = LayerNorm(h, epsilon=cfg.layer_norm_eps)
        # fused qkv: one column-parallel matmul [h, 3h] (reference fuses the
        # same way in fused_attention)
        self.qkv = ColumnParallelLinear(h, 3 * h, gather_output=False,
                                        has_bias=cfg.use_bias)
        self.out_proj = RowParallelLinear(h, h, input_is_parallel=True,
                                          has_bias=cfg.use_bias)
        self.ln_2 = LayerNorm(h, epsilon=cfg.layer_norm_eps)
        self.fc_in = ColumnParallelLinear(h, cfg.ffn_size, gather_output=False,
                                          has_bias=cfg.use_bias)
        self.fc_out = RowParallelLinear(cfg.ffn_size, h, input_is_parallel=True,
                                        has_bias=cfg.use_bias)
        self.drop = Dropout(cfg.dropout)

    def _attn(self, x, cache=None):
        cfg = self.cfg
        b, s, h = x.shape
        qkv = self.qkv(x)  # [b, s, 3h] mp-sharded on last dim
        qkv = qkv.reshape(b, s, 3, cfg.num_heads, cfg.head_dim)
        # keep heads mp-sharded: [b, s, heads/mp, d]
        qkv = _maybe_constraint(qkv, P(None, None, None, "mp", None))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        new_cache = None
        if cache is not None:
            pk, pv, pos = cache
            # pos may be a scalar (dense batch) or a [b] vector of per-row
            # offsets (ragged continuous batching) — models/kv_cache.py
            # decode: the routed decode-attention path (pallas streaming
            # kernel or its exact-semantics dense form, kernels/routing.py),
            # which appends the chunk to the cache on its way (inside
            # the kernel where it reads the slab in place) — seq_lens =
            # pos + s with the causal tail gives precisely the per-query
            # mask (query at chunk offset t sees keys up to pos + t),
            # without materializing a [*, s, S_max] mask tensor
            from ..kernels.decode_attention import append_and_attend
            out, k, v = append_and_attend(q, k, v, pk, pv, pos)
            new_cache = (k, v, pos + s)
        elif cfg.cp:
            # long-context: sequence sharded over the sep axis; ring or
            # Ulysses attention instead of local sdpa (attn dropout is not
            # supported across the ring, matching the ring-flash reference)
            from ..distributed.meta_parallel.context_parallel import (
                ring_attention, ulysses_attention)
            attn = {"ring": ring_attention, "ulysses": ulysses_attention}[cfg.cp]
            out = attn(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 dropout_p=cfg.attn_dropout,
                                                 training=self.training)
        out = out.reshape(b, s, h)
        out = _maybe_constraint(out, P(None, None, "mp"))
        return self.out_proj(out), new_cache

    def forward(self, x, cache=None):
        cfg = self.cfg
        if cfg.sp:
            from ..distributed.meta_parallel.sequence_parallel import seq_sharded
            # LN/dropout run seq-sharded ([b, s/mp, h] — batch-major variant)
            x = _maybe_constraint(x, P(None, "mp", None))
        # a part's scope holds its branch's residual add: XLA makes the
        # add the root of the last projection's fusion, and a fusion is
        # known by its root (obs/parts.py)
        y = self.ln_1(x)
        with jax.named_scope("attention"):
            a, new_cache = self._attn(y, cache)
            x = x + self.drop(a)
        y = self.ln_2(x)
        with jax.named_scope("mlp"):
            m = self.fc_out(F.gelu(self.fc_in(y), approximate=True))
            x = x + self.drop(m)
        if cache is not None:
            return x, new_cache
        return x

    def fused_decode_step(self, x, cache):
        """One decode token through the fused decode-block kernel pair
        (kernels/decode_block.py): norm -> QKV -> in-kernel KV append ->
        streaming attention -> out-proj -> MLP, activations VMEM-
        resident.  ``cache`` is the slot-slab tuple ``(k, v, pos)`` with
        per-row positions; the slabs are updated in place via kernel
        aliasing.  Same contract as the ``forward(cache=...)`` path for
        sq=1 — callers gate on ``fused_decode_supported``."""
        from ..kernels.decode_block import decode_block_layer
        cfg = self.cfg
        h = cfg.hidden_size
        pk, pv, pos = cache
        wqkv = self.qkv.weight                  # [h, 3h]: q | k | v cols
        bqkv = self.qkv.bias
        bq, bk, bv = ((bqkv[:h], bqkv[h:2 * h], bqkv[2 * h:])
                      if bqkv is not None else (None, None, None))
        y, k2, v2 = decode_block_layer(
            x, pk, pv, pos, kv_heads=cfg.num_heads, head_dim=cfg.head_dim,
            norm="layer", eps1=cfg.layer_norm_eps, eps2=cfg.layer_norm_eps,
            norm1_w=self.ln_1.weight, norm1_b=self.ln_1.bias,
            wq=wqkv[:, :h], wk=wqkv[:, h:2 * h], wv=wqkv[:, 2 * h:],
            bq=bq, bkv=bk, bv=bv,
            wo=self.out_proj.weight, bo=self.out_proj.bias,
            norm2_w=self.ln_2.weight, norm2_b=self.ln_2.bias,
            w1=self.fc_in.weight, b1=self.fc_in.bias,
            w2=self.fc_out.weight, b2=self.fc_out.bias,
            act="gelu_tanh")
        return y, (k2, v2, pos + 1)


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = VocabParallelEmbedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = Dropout(cfg.dropout)
        self.h = LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def embed(self, input_ids, position_offset: int = 0):
        b, s = input_ids.shape
        # written as offset + static arange so position_offset may be a
        # traced value (the generate() scan carries it); a [b] offset
        # vector gives per-row positions (ragged continuous batching)
        with jax.named_scope("embed"):
            off = jnp.asarray(position_offset)
            pos = off[..., None] + jnp.arange(s)
            if pos.ndim == 1:
                pos = pos[None, :]
            x = self.wte(input_ids) + self.wpe(pos)
            return self.drop(x)

    def forward(self, input_ids, caches=None):
        from ..distributed.recompute import remat_wrap
        x = self.embed(input_ids)
        new_caches = []
        for i, block in enumerate(self.h):
            if caches is None:
                # cfg.remat applies per block in the training forward
                # (decode/cached path never rematerializes)
                x = remat_wrap(block, self.cfg.remat)(x)
            else:
                x, c = block(x, caches[i])
                new_caches.append(c)
        x = self.ln_f(x)
        return x if caches is None else (x, new_caches)


class GPTForCausalLM(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_embeddings:
            self.lm_head = ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size,
                                                gather_output=False,
                                                has_bias=False)

    def logits(self, hidden):
        with jax.named_scope("head"):
            if self.cfg.tie_embeddings:
                w = self.gpt.wte.weight  # [vocab, h] mp-sharded on vocab
                lg = jnp.einsum("bsh,vh->bsv", hidden, w)
                return _maybe_constraint(lg, P(None, None, "mp"))
            return self.lm_head(hidden)

    def forward(self, input_ids):
        hidden = self.gpt(input_ids)
        return self.logits(hidden)

    def loss(self, input_ids, labels):
        """Vocab-parallel causal LM loss (mean over tokens)."""
        logits = self(input_ids)
        per_tok = parallel_cross_entropy(logits, labels)
        return jnp.mean(per_tok)

    def chunked_loss(self, input_ids, labels, n_chunks: int = 8):
        """Causal LM loss WITHOUT materializing [b, s, V] logits: the
        tied head + softmax CE run chunked over the vocabulary
        (nn.functional.chunked_softmax_cross_entropy).  The single-
        device memory lever: at the flagship bench shape the dense
        logits + grad cost ~3.3 GB of HBM.  Requires tied embeddings
        (the chunked kernel takes the [V, h] table directly)."""
        if not self.cfg.tie_embeddings:
            raise ValueError("chunked_loss needs tie_embeddings=True")
        from ..nn.functional import chunked_softmax_cross_entropy
        hidden = self.gpt(input_ids)
        b, s, h = hidden.shape
        per_tok = chunked_softmax_cross_entropy(
            hidden.reshape(b * s, h), self.gpt.wte.weight,
            labels.reshape(-1), n_chunks=n_chunks)
        return jnp.mean(per_tok)

    # ---- decode (fused_multi_transformer equivalent) -------------------
    def init_cache(self, batch: int, max_len: int, dtype=None):
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        return [(jnp.zeros((batch, max_len, cfg.num_heads, cfg.head_dim), dt),
                 jnp.zeros((batch, max_len, cfg.num_heads, cfg.head_dim), dt),
                 jnp.asarray(0, jnp.int32)) for _ in range(cfg.num_layers)]

    def decode_step(self, input_ids, caches, position: int):
        """One incremental token step; returns (logits, new_caches)."""
        x = self.gpt.embed(input_ids, position)
        new_caches = []
        for block, cache in zip(self.gpt.h, caches):
            x, c = block(x, cache)
            new_caches.append(c)
        x = self.gpt.ln_f(x)
        return self.logits(x), new_caches

    def fused_decode_supported(self, batch: int = 1,
                               kv_len: Optional[int] = None,
                               tp: int = 1):
        """Static legality of the fused decode-block path for this
        config at ``(batch, kv_len)``; ``tp > 1`` checks the sharded
        variant's per-shard plan (kernels/decode_block_tp.py).
        Returns ``(ok, reason)``."""
        from ..kernels.decode_block import fusion_legal
        cfg = self.cfg
        if cfg.dropout and self.training:
            return False, "dropout active (training mode)"
        return fusion_legal(
            max_seq=kv_len or cfg.max_seq_len, hidden=cfg.hidden_size,
            heads=cfg.num_heads, kv_heads=cfg.num_heads,
            head_dim=cfg.head_dim, ffn=cfg.ffn_size, batch=batch,
            dtype=cfg.dtype, tp=tp)

    def fused_decode_step(self, input_ids, caches, position):
        """``decode_step`` through the fused decode-block kernels: the
        embed / final-norm / logits legs are shared code, each layer
        body runs as the Pallas kernel pair with the KV slabs updated
        in-kernel.  Per-row ``position`` vectors (continuous batching)
        and scalars both work."""
        x = self.gpt.embed(input_ids, position)
        new_caches = []
        for block, cache in zip(self.gpt.h, caches):
            x, c = block.fused_decode_step(x, cache)
            new_caches.append(c)
        x = self.gpt.ln_f(x)
        return self.logits(x), new_caches

    def generate(self, input_ids, max_new_tokens: int, **kw):
        """Single-scan autoregressive decoding (models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, **kw)

    # ---- tensor-parallel serving (serving/tp.py) ----------------------
    def tp_decode_supported(self, tp: int):
        """Static legality of the fused compute-collective TP decode
        program at degree ``tp``: every partitioned dimension must tile
        the mesh axis evenly (fixed shapes per device — the same
        discipline as the engine's compile-count pin).  Returns
        ``(ok, reason)``."""
        cfg = self.cfg
        for what, n in (("num_heads", cfg.num_heads),
                        ("ffn_size", cfg.ffn_size),
                        ("vocab_size", cfg.vocab_size)):
            if n % tp:
                return False, (f"{what} {n} not divisible by "
                               f"tensor_parallel {tp}")
        return True, None

    def tp_decode_weights(self, tp: int):
        """``(arch, weights)`` for the serving TP decode program
        (serving/tp.py).  The fused QKV weight is re-arranged so each
        device's contiguous column shard is ``[q_d | k_d | v_d]`` for
        its own head group — the manual program needs head-aligned
        blocks, which the training layout's plain contiguous split of
        the fused ``[h, 3h]`` matrix does not give."""
        cfg = self.cfg
        h, dh = cfg.hidden_size, cfg.head_dim
        arch = {"norm": "layer", "eps": cfg.layer_norm_eps,
                "act": "gelu_tanh", "rope": False, "rope_theta": None,
                "heads": cfg.num_heads, "kv_heads": cfg.num_heads,
                "head_dim": dh, "hidden": h, "vocab": cfg.vocab_size}
        step = (cfg.num_heads // tp) * dh
        blocks = []
        for blk in self.gpt.h:
            w, bias = blk.qkv.weight, blk.qkv.bias
            wq, wk, wv = w[:, :h], w[:, h:2 * h], w[:, 2 * h:]
            parts, bparts = [], []
            for d in range(tp):
                sl = slice(d * step, (d + 1) * step)
                parts += [wq[:, sl], wk[:, sl], wv[:, sl]]
                if bias is not None:
                    bparts += [bias[:h][sl], bias[h:2 * h][sl],
                               bias[2 * h:][sl]]
            blocks.append({
                "n1w": blk.ln_1.weight, "n1b": blk.ln_1.bias,
                "wqkv": jnp.concatenate(parts, axis=1),
                "bqkv": jnp.concatenate(bparts) if bias is not None
                else None,
                "wo": blk.out_proj.weight, "bo": blk.out_proj.bias,
                "n2w": blk.ln_2.weight, "n2b": blk.ln_2.bias,
                "wup": blk.fc_in.weight, "bup": blk.fc_in.bias,
                "wdown": blk.fc_out.weight, "bdown": blk.fc_out.bias})
        return arch, {
            "wte": self.gpt.wte.weight, "wpe": self.gpt.wpe.weight,
            "head": None if cfg.tie_embeddings else self.lm_head.weight,
            "nfw": self.gpt.ln_f.weight, "nfb": self.gpt.ln_f.bias,
            "blocks": blocks}


def gpt_tiny(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, **kw)


def gpt_small(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, max_seq_len=1024, **kw)


def gpt3_6_7b(**kw) -> GPTConfig:
    # GPT-3 6.7B: 32 layers, 4096 hidden, 32 heads, 2048 seq
    return GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=32,
                     num_heads=32, max_seq_len=2048, dtype="bfloat16", **kw)
