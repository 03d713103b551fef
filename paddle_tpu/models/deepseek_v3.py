"""DeepSeek-V3-shaped decoders (DeepSeek-V3, arXiv:2412.19437;
``modeling_deepseek_v3.py`` of transformers; the family JoyAI-LLM-Flash's
``config.json`` names by its keys): latent attention (MLA) over ONE
cached row a position, and dropless sparse experts beside a shared one.

Every layer is pre-norm, ``a = x + Attn(N1(x))``, ``y = a + FFN(N2(a))``
with the RMSNorm of models/llama.py (the residual stream ``x`` float32
between layers, the branches in the model's dtype, each branch's output
left in its matmul's float32 accumulator).  The first
``first_k_dense_replace`` layers' FFN is the SiLU-gated MLP of
models/llama.py (its weights' names, its function; as
``moe_dropless.GatedMLP``, which keeps the gate and the output in
float32) at ``intermediate_size``; every later layer's is
``distributed.moe_dropless.DroplessMoE``: ``n_routed_experts`` gated
MLPs of ``moe_intermediate_size``, ``num_experts_per_tok`` chosen by
sigmoid scores, one shared expert, nothing dropped.

Latent attention, per position ``t`` with normed input ``u_t``:
``cq = N_q(W_dq u_t)``; ``[qn_i, qr_i] = W_uq cq`` per head ``i``;
``[ckv_t, kr_t] = W_dkv u_t``; ``c_t = N_kv(ckv_t)``; ``[kn_ti, v_ti] =
W_ukv c_t`` per head; ``q_i = [qn_i, R_t(qr_i)]``, ``k_ti = [kn_ti,
R_t(kr_t)]`` (one rotary key for all heads); causal softmax of ``q_i .
k_si / sqrt(nope + rope)``; ``out = W_o [o_1 .. o_heads]``.

What a position keeps is ``[c_t, R_t(kr_t)]``: ``kv_lora_rank +
qk_rope_head_dim`` values a layer (576 at the published widths, against
``heads x (192 + 128)`` = 10,240 for K and V), held padded with zeros
to whole 128-lane tiles (640: what the device's tiled layout takes for
a 576-wide row anyway).  The cache is therefore
ONE row kind a layer, ``(rows [b, max_len, 1, 640], None, pos)``: the
``v`` of the ``(k, v, pos)`` tuple every other decoder carries is
``None`` (``cfg.cache_row_kinds`` 1; serving/kv_pool.py makes the pool
from it).  The attention is computed from those rows in the ABSORBED
form: ``ql_i = W_uk,i^T qn_i``, score ``= ql_i . c_s + R(qr_i) .
R(kr_s)``, ``ol_i = sum_s p_s c_s``, ``o_i = W_uv,i ol_i``: one shared
576-wide "KV head" under all the query heads whose V is the first
``kv_lora_rank`` columns of its K, never expanded.  A decode step runs
it in ``kernels/latent_attention.py`` (the slot's live rows streamed
once, the fresh row appended in place), a cached chunk in the same
module's chunk kernel (each query tile streams the rows up to its own
causal edge), a chunk that attends to itself alone as two XLA dots.  The
EXPANDED form (K and V rebuilt per head from the rows held, 192-wide
scores) is what the plain reference of
``benchmarks/builders/deepseek_v3.py`` computes and the tests hold this
one against; as a program it was slower at every chunk width on the
chip (one ``W_ukv`` product over EVERY row of the slot whatever the
width: 27.4 / 42.6 / 74.8 ms against 23.1 / 35.4 / 61.0 for a chunk of
512 / 1024 / 2048 tokens, PERF.md section 6, PR 34) and is not kept.

Rotary pairing: the published model rotates the pairs ``(2j, 2j+1)``
(``rope_interleave``).  Here the rotary columns of ``q`` and of the key
are DE-INTERLEAVED first (even columns, then odd) and then rotated by
models/llama.py's ``apply_rotary_pos_emb`` (pairs ``(j, j + d/2)``): the
same rotation in a column order that ``q`` and ``k`` share, so every
score is the published one, and the cached rotary key is held in that
order (a layout).

The multi-token-prediction module (``num_nextn_predict_layers``) takes no
part in the published inference forward and is not built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..distributed.moe_dropless import (DroplessMoE, GatedMLP, _wide,
                                        grouped_matmul_route)
from ..kernels.latent_attention import (attended_rows,
                                        latent_attention_route,
                                        latent_chunk_attention,
                                        latent_chunk_route,
                                        latent_decode_attention)
from ..nn import initializer as I
from ..nn.layer import Layer, ParamAttr
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.norm import RMSNorm
from .kv_cache import append_rows, cache_lens
from .llama import _rope_tables, apply_rotary_pos_emb

__all__ = ["DeepseekV3Config", "DeepseekV3Attention", "DeepseekV3DecoderLayer",
           "DeepseekV3Model", "DeepseekV3ForCausalLM", "deepseek_v3_tiny"]

# query rows (tokens) one block of a chunk's attention takes: the
# float32 scores of a block are [heads, block, max_len]
ATTN_QUERY_BLOCK = 512


@dataclasses.dataclass
class DeepseekV3Config:
    """Defaults are jdopensource/JoyAI-LLM-Flash's ``config.json``
    (float32 until a caller names the serving dtype)."""
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    # normal(0, initializer_range) for the embedding, the head and every
    # matrix but the routed experts' ``down_proj`` (None: as the others);
    # see :meth:`routed_out`
    initializer_range: float = 0.02
    routed_out_range: Optional[float] = None
    max_seq_len: int = 131072
    dtype: str = "float32"

    def __post_init__(self):
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: "
                f"only one group is implemented (grouped top-k limits "
                f"the choice to the best groups first)")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    # ---- what the serving pool reads (serving/kv_pool.py) --------------
    @property
    def kv_heads(self) -> int:
        return 1            # the latent row is shared by every query head

    @property
    def cache_row_kinds(self) -> int:
        return 1            # one row a position, not a K and a V

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_width(self) -> int:
        """The latent row as the cache holds it: padded with zeros to
        whole 128-lane tiles (576 -> 640).  The device's tiled layout
        holds a 576-wide row in 640 lanes whatever its logical shape,
        and a kernel's DMA must move whole tiles, so the padding costs
        no memory and is stated."""
        return -(-self.latent_width // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def routed_out(self) -> float:
        """The std of a ROUTED expert's ``down_proj``.  With every
        matrix at one std ONE routed expert is a tenth of the stream it
        adds to, and a near-tie between the last chosen expert and the
        first left out, which any rounding upstream flips, moves the
        logits by a tenth of their scale (measured: PERF.md section 6,
        PR 34): a caller that compares logits draws these smaller."""
        return self.initializer_range if self.routed_out_range is None \
            else self.routed_out_range

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - min(self.first_k_dense_replace,
                                     self.num_layers)

    def num_params(self) -> int:
        h, heads = self.hidden_size, self.num_heads
        attn = h * self.q_lora_rank + self.q_lora_rank \
            + self.q_lora_rank * heads * self.qk_head_dim \
            + h * self.latent_width + self.kv_lora_rank \
            + self.kv_lora_rank * heads * (self.qk_nope_head_dim
                                           + self.v_head_dim) \
            + heads * self.v_head_dim * h
        expert = 3 * h * self.moe_intermediate_size
        moe = (self.n_routed_experts + self.n_shared_experts) * expert \
            + h * self.n_routed_experts + self.n_routed_experts
        dense = 3 * h * self.intermediate_size
        n_moe = self.num_expert_layers
        return 2 * self.vocab_size * h + h \
            + self.num_layers * (attn + 2 * h) \
            + n_moe * moe + (self.num_layers - n_moe) * dense


def _deinterleave(x):
    """Columns ``(0, 2, 4, .., 1, 3, 5, ..)`` of the last axis."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _seen(lens, s: int, max_len: int, q0: int = 0, qn: Optional[int] = None):
    """``[b, qn, max_len]`` bool: cached row ``r`` is seen by query ``q0
    + i`` of a chunk of ``s`` appended tokens iff ``r <= lens - s + q0 +
    i`` (``lens [b]`` the rows held AFTER the append)."""
    qn = s if qn is None else qn
    last = (lens - s)[:, None] + q0 + jnp.arange(qn)[None, :]
    return jnp.arange(max_len)[None, None, :] <= last[..., None]


class DeepseekV3Attention(Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        h, heads = cfg.hidden_size, cfg.num_heads
        # no rope scaling, so no ``mscale`` on it
        self.softmax_scale = 1.0 / math.sqrt(cfg.qk_head_dim)
        w = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        self.q_a_proj = Linear(h, cfg.q_lora_rank, weight_attr=w,
                               bias_attr=False)
        self.q_a_layernorm = RMSNorm(cfg.q_lora_rank,
                                     epsilon=cfg.rms_norm_eps)
        self.q_b_proj = Linear(cfg.q_lora_rank, heads * cfg.qk_head_dim,
                               weight_attr=w, bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(h, cfg.latent_width,
                                         weight_attr=w, bias_attr=False)
        self.kv_a_layernorm = RMSNorm(cfg.kv_lora_rank,
                                      epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = Linear(
            cfg.kv_lora_rank,
            heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            weight_attr=w, bias_attr=False)
        self.o_proj = Linear(heads * cfg.v_head_dim, h, weight_attr=w,
                             bias_attr=False)

    def _queries_and_row(self, x, cos, sin):
        """``x [b, s, h]`` (normed, the weights' dtype) -> ``(qn [b, s,
        heads, nope], qr [b, s, heads, rope] rotated, row [b, s, 1,
        lora + rope])``, the row as a position caches it."""
        cfg = self.cfg
        b, s, _ = x.shape
        dt = x.dtype
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        q = q.reshape(b, s, cfg.num_heads, cfg.qk_head_dim)
        qn, qr = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
        ckv = self.kv_a_proj_with_mqa(x)
        c = self.kv_a_layernorm(ckv[..., :cfg.kv_lora_rank])
        kr = ckv[..., None, cfg.kv_lora_rank:]               # [b, s, 1, rope]
        # the rotation in float32, on the de-interleaved columns
        qr = apply_rotary_pos_emb(_deinterleave(qr).astype(jnp.float32),
                                  cos, sin).astype(dt)
        kr = apply_rotary_pos_emb(_deinterleave(kr).astype(jnp.float32),
                                  cos, sin).astype(dt)
        pad = jnp.zeros((b, s, 1, cfg.cache_row_width - cfg.latent_width),
                        dt)
        row = jnp.concatenate([c[:, :, None, :], kr, pad], axis=-1)
        return qn, qr, row

    def _up(self):
        """``W_ukv`` as ``(W_uk, W_uv) [lora, heads, nope | v]``."""
        cfg = self.cfg
        w = self.kv_b_proj.weight.reshape(
            cfg.kv_lora_rank, cfg.num_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def _absorbed_query(self, qn, qr, w_uk):
        """``[W_uk^T qn, R(qr), 0] [b, s, heads, row width]``."""
        cfg = self.cfg
        ql = jnp.einsum("bshd,chd->bshc", qn, w_uk).astype(qr.dtype)
        pad = jnp.zeros(qr.shape[:-1]
                        + (cfg.cache_row_width - cfg.latent_width,),
                        qr.dtype)
        return jnp.concatenate([ql, qr, pad], axis=-1)

    def absorbed(self, qn, qr, rows, seen):
        """Attention against the latent rows themselves.  ``rows [b,
        len, lora + rope]``, ``seen [b, s, len]`` -> ``[b, s, heads,
        v]`` float32."""
        cfg = self.cfg
        w_uk, w_uv = self._up()
        dt = rows.dtype
        q = self._absorbed_query(qn, qr, w_uk)               # [b,s,H,576]
        scores = jnp.einsum("bshc,brc->bhsr", q, rows,
                            preferred_element_type=jnp.float32)
        scores = scores * self.softmax_scale
        scores = jnp.where(seen[:, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(dt)
        ol = jnp.einsum("bhsr,brc->bshc", p, rows[..., :cfg.kv_lora_rank],
                        preferred_element_type=jnp.float32).astype(dt)
        return jnp.einsum("bshc,chv->bshv", ol, w_uv,
                          preferred_element_type=jnp.float32)

    def forward(self, x, cos, sin, cache=None):
        """``x [b, s, h]``; ``cache``: None (the chunk attends to itself
        alone) or ``(rows [b, max_len, 1, w], None, pos)``.  Returns
        ``(out [b, s, h] float32, cache')``."""
        cfg = self.cfg
        b, s, _ = x.shape
        qn, qr, row = self._queries_and_row(x, cos, sin)
        if cache is not None and s == 1 and latent_attention_route(
                cache[0].shape, cache[0].dtype)[0] == "latent_in_place":
            # a decode step: the kernel appends the row and attends
            buf, _, pos = cache
            w_uk, w_uv = self._up()
            ol, buf = latent_decode_attention(
                self._absorbed_query(qn, qr, w_uk)[:, 0], row, buf,
                jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)),
                lora=cfg.kv_lora_rank, scale=self.softmax_scale)
            o = jnp.einsum("bhc,chv->bhv", ol.astype(x.dtype), w_uv,
                           preferred_element_type=jnp.float32)
            o = o.astype(x.dtype).reshape(b, 1,
                                          cfg.num_heads * cfg.v_head_dim)
            return _wide(self.o_proj, o), (buf, None, pos + 1)
        if cache is None:
            held, lens, new_cache = row[:, :, 0], jnp.full((b,), s), None
        else:
            buf, _, pos = cache
            with jax.named_scope("kv_append"):
                buf = append_rows(buf, row, pos)
            held, lens = buf[:, :, 0], cache_lens(pos, s, b)
            new_cache = (buf, None, pos + s)
        if cache is not None and latent_chunk_route(
                buf.shape, s, buf.dtype)[0] == "latent_chunk":
            # the chunk's queries read the rows up to their own causal
            # edge, not all max_len
            w_uk, w_uv = self._up()
            ol = latent_chunk_attention(
                self._absorbed_query(qn, qr, w_uk), buf,
                jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)),
                lora=cfg.kv_lora_rank, scale=self.softmax_scale)
            o = jnp.einsum("bshc,chv->bshv", ol.astype(x.dtype), w_uv,
                           preferred_element_type=jnp.float32)
        else:
            outs = []
            for q0 in range(0, s, ATTN_QUERY_BLOCK):
                q1 = min(q0 + ATTN_QUERY_BLOCK, s)
                outs.append(self.absorbed(
                    qn[:, q0:q1], qr[:, q0:q1], held,
                    _seen(lens, s, held.shape[1], q0, q1 - q0)))
            o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        o = o.astype(x.dtype).reshape(b, s, cfg.num_heads * cfg.v_head_dim)
        return _wide(self.o_proj, o), new_cache


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, cfg: DeepseekV3Config, layer_index: int):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                epsilon=cfg.rms_norm_eps)
        self.sparse = layer_index >= cfg.first_k_dense_replace
        if self.sparse:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                n_shared=cfg.n_shared_experts,
                routed_scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob,
                init_std=cfg.initializer_range,
                routed_out_std=cfg.routed_out)
        else:
            self.mlp = GatedMLP(cfg.hidden_size, cfg.intermediate_size,
                                cfg.initializer_range)

    def forward(self, x, cos, sin, cache=None, live=None):
        """``x [b, s, h]`` float32; ``live [b, s]`` bool: the tokens that
        are routed (None: all).  Returns ``(x, cache', rows)``, ``rows
        [experts]`` the live rows each expert got (None: a dense
        layer)."""
        b, s, h = x.shape
        dt = self.input_layernorm.weight.dtype
        y = self.input_layernorm(x).astype(dt)
        # a branch's scope holds its residual add: a fusion is known by
        # its root (obs/parts.py); the expert layer names its own parts
        with jax.named_scope("attention"):
            a, cache = self.self_attn(y, cos, sin, cache)
            x = x + a
        u = self.post_attention_layernorm(x)             # float32
        if self.sparse:
            y, rows = self.mlp(u.reshape(b * s, h), None if live is None
                               else live.reshape(b * s))
            with jax.named_scope("experts"):
                return x + y.reshape(b, s, h), cache, rows
        with jax.named_scope("mlp"):
            return x + self.mlp(u.astype(dt)), cache, None


class DeepseekV3Model(Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=ParamAttr(
                initializer=I.Normal(0.0, cfg.initializer_range)))
        self.layers = LayerList([DeepseekV3DecoderLayer(cfg, i)
                                 for i in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None, position=0, valid=None):
        """``valid``: how many leading tokens of each row are real (a
        scalar, or ``[b]``; None: all): the others are routed to no
        expert.  Returns ``(hidden, caches', rows [expert layers,
        experts] int32)``."""
        cfg = self.cfg
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            emb = self.embed_tokens(input_ids)
        with jax.named_scope("attention"):
            pos = jnp.asarray(position)[..., None] + jnp.arange(s)
            cos, sin = _rope_tables(pos, cfg.qk_rope_head_dim,
                                    cfg.rope_theta, jnp.float32)
        live = None
        if valid is not None:
            live = jnp.arange(s)[None, :] < jnp.broadcast_to(
                jnp.asarray(valid, jnp.int32), (b,))[:, None]
        x = emb.astype(jnp.float32)
        new_caches, rows = [], []
        for i, layer in enumerate(self.layers):
            x, c, r = layer(x, cos, sin,
                            None if caches is None else caches[i], live)
            new_caches.append(c)
            if r is not None:
                rows.append(r)
        rows = jnp.stack(rows) if rows else jnp.zeros(
            (0, cfg.n_routed_experts), jnp.int32)
        return self.norm(x).astype(emb.dtype), new_caches, rows


class _Unrouted:
    """``generate``'s view of the model: ``decode_step`` without the
    expert rows."""

    def __init__(self, model):
        self.model, self.cfg = model, model.cfg
        self.init_cache = model.init_cache

    def decode_step(self, input_ids, caches, position):
        return self.model.decode_step(input_ids, caches, position)[:2]


class DeepseekV3ForCausalLM(Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.model = DeepseekV3Model(cfg)
        self.lm_head = Linear(
            cfg.hidden_size, cfg.vocab_size, bias_attr=False,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, cfg.initializer_range)))

    def _head(self, hidden):
        with jax.named_scope("head"):
            return self.lm_head(hidden)

    def forward(self, input_ids):
        return self._head(self.model(input_ids)[0])

    # ---- what a request carries ----------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None):
        """One ``(rows, None, pos)`` a layer: the latent row is the only
        kind a position holds."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        return [(jnp.zeros((batch, max_len, 1, cfg.cache_row_width), dt),
                 None, jnp.asarray(0, jnp.int32))
                for _ in range(cfg.num_layers)]

    def decode_step(self, input_ids, caches, position, valid=None):
        """``input_ids [b, s]`` appended at ``position`` (a scalar or
        ``[b]``).  ``valid``: the count of real leading tokens of each
        row (a right-padded chunk's, or 0 for a parked serving slot's
        ride-along token); the others reach no expert.  Returns
        ``(logits, caches', rows)``, ``rows [expert layers, experts]``
        int32 the live rows each expert got."""
        caches = [(k, None, position) for k, _, _ in caches]
        hidden, caches, rows = self.model(input_ids, caches, position,
                                          valid)
        return self._head(hidden), caches, rows

    # ---- what the serving engine reads ---------------------------------
    def expert_routing_spec(self):
        """``(expert layers, experts)``: the model routes, its
        ``decode_step`` takes ``valid=`` and returns the rows each
        expert got (serving/engine.py counts them)."""
        return self.cfg.num_expert_layers, self.cfg.n_routed_experts

    def expert_route(self, rows: int):
        """``(route, reason)`` of the grouped matmul in a program of
        ``rows`` tokens (static per compiled program;
        ``distributed.moe_dropless.grouped_matmul_route``)."""
        cfg = self.cfg
        return grouped_matmul_route(
            rows * cfg.num_experts_per_tok, cfg.hidden_size,
            cfg.moe_intermediate_size, cfg.dtype)

    def attention_route(self, slab_shape, dtype):
        """``(route, reason)`` of a decode step's attention over the
        latent slabs (``kernels.latent_attention``)."""
        return latent_attention_route(slab_shape, dtype)

    def chunk_attention_route(self, staging_shape, width: int, dtype):
        """``(route, reason)`` of a cached prefill chunk's attention over
        a request's latent staging (``kernels.latent_attention``)."""
        return latent_chunk_route(staging_shape, width, dtype)

    def attended_rows(self, staging_shape, offset: int, width: int,
                      dtype) -> int:
        """Staging rows a layer of that chunk's program reads: whole row
        tiles up to the chunk's last row on the kernel, every row on
        ``xla_dense``."""
        max_seq = staging_shape[1]
        if latent_chunk_route(staging_shape, width, dtype)[0] \
                == "latent_chunk":
            return attended_rows(max_seq, offset, width)
        return max_seq

    def serving_refusals(self) -> dict:
        """Engine features that hold K and V rows of one shape, or a
        layout this model has none of, each with its reason."""
        return {
            "prefix_cache": (
                "the block pool and the fleet handoff hold K and V "
                "blocks; a cached position here is ONE latent row "
                "(needs a block pool made from the model's row kinds)"),
            "speculation": (
                "the verify window's program neither passes the live "
                "mask into the expert layers nor counts their rows "
                "(the window itself would roll back: rows, no state)"),
            "tensor_parallel": (
                "one latent row a position does not partition on a "
                "kv-head axis, and the experts have no layout in the "
                "serving mesh"),
            "aot_store": (
                "the store's programs take K and V slab lists; the "
                "latent cache's programs carry one row list and the "
                "expert-load counter"),
        }

    def fused_decode_supported(self, batch: int = 1,
                               kv_len: Optional[int] = None, tp: int = 1):
        return False, ("latent attention and expert layers: the fused "
                       "decode block computes K/V attention and a dense "
                       "MLP")

    def generate(self, input_ids, max_new_tokens: int, **kw):
        """Single-scan autoregressive decoding (models/generation.py)."""
        from .generation import generate
        return generate(_Unrouted(self), input_ids, max_new_tokens, **kw)


def deepseek_v3_tiny(**kw) -> DeepseekV3Config:
    """A dense first layer and two expert layers, 8 experts top-2, one
    shared; ranks and head widths that all differ."""
    return DeepseekV3Config(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_layers=3, num_heads=4,
        q_lora_rank=40, kv_lora_rank=32, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
        num_experts_per_tok=2, max_seq_len=128), **kw})
