"""Jamba — a hybrid decoder: state-space (Mamba-1) layers with an
attention layer every ``attn_layer_period`` (AI21 Jamba / Jamba2,
``config.json`` and ``modeling_jamba.py`` on Hugging Face).

Every layer is pre-norm, ``a = x + Mixer(N1(x))``, ``y = a + MLP(N2(a))``
with the SwiGLU MLP and RMSNorm of models/llama.py (the residual stream
``x`` float32 between layers, the branches in the model's dtype); layer
``i`` mixes by
attention iff ``i % attn_layer_period == attn_layer_offset``, by Mamba
otherwise.  There is no positional encoding of any kind: the recurrence
carries the order.

* The attention mixer is grouped-query causal attention without rotary
  and without bias, through the same cached call the other decoders
  make (``kernels.decode_attention.append_and_attend``).
* The Mamba mixer, per position ``t`` of the normed input ``u_t``:
  ``[x_t, z_t] = W_in u_t``; ``c_t = silu(b_conv + sum_j w_conv[j] *
  x_{t-3+j})`` (depthwise causal convolution over ``d_conv`` positions);
  ``[dt_t, B_t, C_t] = W_x c_t``, each through its own RMSNorm (Jamba's
  addition to Mamba-1); ``delta_t = softplus(W_dt dt_t + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(delta_t (x) A) h_{t-1} + (delta_t
  c_t) (x) B_t``; ``y_t = h_t C_t + D c_t``; ``out_t = W_out (y_t *
  silu(z_t))``.  ``delta``, ``A``, the state and the sum over
  ``d_state`` are float32 whatever the model's dtype.

What a request carries from token to token is therefore of TWO kinds: K
and V rows for the few attention layers (``init_cache``: one ``(k, v,
pos)`` plane per ATTENTION layer, ``cfg.num_cache_layers``), and per
Mamba layer a state of FIXED size, ``h`` (``[d_state, d_inner]``
float32, ``d_inner`` on the lanes) and the convolution's window (the
last ``d_conv - 1`` columns of ``x``): ``init_state`` /
``recurrent_state_spec``.  ``decode_step(ids, caches, pos, state=,
valid=)`` threads both.  A recurrence does not forgive padding: ``valid``
(the count of real tokens in a right-padded chunk) masks ``delta`` to 0
past it, which leaves ``h`` as it was, and takes the window at
``valid``, not at the chunk's width.

The recurrence over a chunk runs by ``kernels.selective_scan`` (the
Pallas kernel, which walks the chunk in blocks of 128 positions with the
state in vregs, or a plain scan where the kernel cannot tile the shape);
over one token it is written out (``one_step``).  The 28 layers are unrolled: their weights
are separate operands that a decode step reads where they lie (a scan
over stacked weights copied 6.9 ms a step out of the stacks in
models/ouro.py's decode, PERF.md section 5).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.decode_attention import append_and_attend
from ..kernels.selective_scan import scan_route, selective_scan
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, ParamAttr
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.norm import RMSNorm
from .llama import LlamaMLP

__all__ = ["JambaConfig", "JambaAttention", "JambaMambaMixer",
           "JambaDecoderLayer", "JambaModel", "JambaForCausalLM",
           "jamba_tiny"]

# query rows (tokens x query heads per kv head) one cached attention call
# of a prefill chunk takes: the matmul kernel holds a call's queries and
# three float32 accumulators of that many rows in VMEM (512 tokens x 20
# heads at once would be 27 MB of its 16), so a chunk attends in
# sub-blocks of this many rows, in order
ATTN_QUERY_ROWS = 2048


def _wide(layer: Linear, x):
    """``layer(x)`` with the product left in the matmul's float32
    accumulator.  Where a result feeds float32 arithmetic anyway (the
    residual add, the convolution and the recurrence, a gate), rounding
    it to the weights' dtype first only adds error: the state-space
    mixer is a fourth-order product of its input (``delta c B C``) and
    doubles a relative perturbation where attention passes it on, so 28
    layers of bfloat16 roundings read 0.044 of the logits' scale off the
    float32 reference on the chip, a pure-attention stack of the same
    depth 0.01 (PERF.md, PR 32)."""
    y = jnp.dot(x, layer.weight, preferred_element_type=jnp.float32)
    return y if layer.bias is None else y + layer.bias.astype(jnp.float32)


@dataclasses.dataclass
class JambaConfig:
    """Defaults are ai21labs/AI21-Jamba2-3B's ``config.json`` (float32
    until a caller names the serving dtype)."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: Optional[int] = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    max_seq_len: int = 262144
    rms_norm_eps: float = 1e-6
    dtype: str = "float32"
    # None: kernels.selective_scan.scan_route picks by shape; a name
    # forces that form of the chunk recurrence (tests, timing)
    scan_form: Optional[str] = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> tuple:
        """``"attention"`` or ``"mamba"`` per layer, by the published
        period/offset rule."""
        return tuple(
            "attention" if i % self.attn_layer_period
            == self.attn_layer_offset else "mamba"
            for i in range(self.num_layers))

    @property
    def num_cache_layers(self) -> int:
        """KV planes one cached position spans: the attention layers
        (the serving pools size themselves from this)."""
        return self.layer_kinds.count("attention")

    @property
    def num_state_layers(self) -> int:
        return self.layer_kinds.count("mamba")

    def num_params(self) -> int:
        h, di, n, r = (self.hidden_size, self.d_inner, self.mamba_d_state,
                       self.mamba_dt_rank)
        kvh = self.kv_heads * self.head_dim
        mamba = h * 2 * di + di * self.mamba_d_conv \
            + di * self.mamba_conv_bias + di * (r + 2 * n) + r * di + di \
            + di * n + di + di * h + r + 2 * n \
            + 2 * di * self.mamba_proj_bias + h * self.mamba_proj_bias
        attn = 2 * h * h + 2 * h * kvh
        mlp = 3 * h * self.intermediate_size
        return self.num_state_layers * (mamba + mlp + 2 * h) \
            + self.num_cache_layers * (attn + mlp + 2 * h) \
            + self.vocab_size * h + h


class _InverseSoftplusOfLogUniform(I.Initializer):
    """``b`` with ``softplus(b)`` log-uniform in ``[lo, hi]``: Mamba's
    published draw of the step's bias."""

    def __init__(self, lo: float = 1e-3, hi: float = 1e-1):
        self.lo, self.hi = lo, hi

    def init(self, key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _LogArange(I.Initializer):
    """``A_log[n, :] = log(n + 1)``: Mamba's S4D-real start."""

    def init(self, key, shape, dtype):
        n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)


def _branch_out(cfg: JambaConfig, fan_in: int, bias=False) -> Linear:
    """The projection that ends a residual branch, drawn ``1 / sqrt(2 x
    layers)`` smaller: Mamba's published initializer (GPT-2's scaling of
    the residual branches, ``rescale_prenorm_residual``).  With every
    branch at full size a randomly drawn stack of these mixers turns its
    stream over at every layer, and bfloat16 rounding reads 0.030 of the
    logits' scale off the float32 reference on the chip (PERF.md,
    PR 32)."""
    return Linear(fan_in, cfg.hidden_size, bias_attr=bias,
                  weight_attr=ParamAttr(initializer=I.XavierNormal(
                      gain=1.0 / math.sqrt(2.0 * cfg.num_layers))))


class JambaAttention(Layer):
    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = Linear(h, cfg.num_heads * d, bias_attr=False)
        self.k_proj = Linear(h, cfg.kv_heads * d, bias_attr=False)
        self.v_proj = Linear(h, cfg.kv_heads * d, bias_attr=False)
        self.o_proj = _branch_out(cfg, cfg.num_heads * d)

    def forward(self, x, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_proj(x).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = self.k_proj(x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
        v = self.v_proj(x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
        new_cache = None
        if cache is not None:
            pk, pv, pos = cache
            rep = cfg.num_heads // cfg.kv_heads
            # the largest power of two of tokens within the row budget
            blk = 1 << max((ATTN_QUERY_ROWS // rep).bit_length() - 1, 0)
            outs = []
            for off in range(0, s, blk):
                w = slice(off, min(off + blk, s))
                o, pk, pv = append_and_attend(q[:, w], k[:, w], v[:, w],
                                              pk, pv, pos + off)
                outs.append(o)
            out = outs[0] if len(outs) == 1 \
                else jnp.concatenate(outs, axis=1)
            new_cache = (pk, pv, pos + s)
        else:
            rep = cfg.num_heads // k.shape[2]
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 training=self.training)
        out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        return _wide(self.o_proj, out), new_cache


def _window_after(xx, valid, keep: int):
    """The convolution's window after a chunk's last REAL token: ``xx
    [b, keep + s, d]`` is the carried window followed by the chunk's
    ``x``, so columns ``valid - keep .. valid - 1`` of ``x`` are rows
    ``valid ..`` of ``xx`` (``valid`` None: the chunk holds no
    padding)."""
    b, rows, d = xx.shape
    if valid is None:
        return xx[:, rows - keep:]
    at = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (b,))
    return jax.vmap(lambda row, a: jax.lax.dynamic_slice(
        row, (a, 0), (keep, d)))(xx, at)


def _mask_padding(delta, valid):
    """``delta [b, s, d]`` with the positions past ``valid`` at 0: a
    padded position leaves the state as it was (``exp(0) = 1`` and
    nothing is added)."""
    if valid is None:
        return delta
    b, s, _ = delta.shape
    at = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (b,))
    live = jnp.arange(s)[None, :] < at[:, None]
    return jnp.where(live[..., None], delta, 0.0)


class JambaMambaMixer(Layer):
    """The state-space mixer.  ``forward(x, state, valid)`` runs ``x
    [b, s, h]`` from ``state = {"conv": [b, d_conv - 1, d_inner],
    "ssm": [b, d_state, d_inner]}`` and returns ``(out, new state)``.
    ``conv_weight`` is ``[d_conv, d_inner]`` and ``A_log``
    ``[d_state, d_inner]``: ``d_inner`` on the lanes."""

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        h, di, n, r, k = (cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state,
                          cfg.mamba_dt_rank, cfg.mamba_d_conv)
        bias = None if cfg.mamba_proj_bias else False
        self.in_proj = Linear(h, 2 * di, bias_attr=bias)
        self.conv_weight = self.create_parameter(
            (k, di), default_initializer=I.Uniform(-1.0 / math.sqrt(k),
                                                   1.0 / math.sqrt(k)))
        if cfg.mamba_conv_bias:
            self.conv_bias = self.create_parameter((di,), is_bias=True)
        else:
            self.conv_bias = None
            self.add_parameter("conv_bias", None)
        self.x_proj = Linear(di, r + 2 * n, bias_attr=False)
        self.dt_proj = Linear(r, di)
        self.dt_proj.bias = self.dt_proj.create_parameter(
            (di,), default_initializer=_InverseSoftplusOfLogUniform())
        self.A_log = self.create_parameter(
            (n, di), default_initializer=_LogArange())
        self.D = self.create_parameter(
            (di,), default_initializer=I.Constant(1.0))
        self.out_proj = _branch_out(cfg, di, bias)
        self.dt_layernorm = RMSNorm(r, epsilon=cfg.rms_norm_eps)
        self.b_layernorm = RMSNorm(n, epsilon=cfg.rms_norm_eps)
        self.c_layernorm = RMSNorm(n, epsilon=cfg.rms_norm_eps)

    def forward(self, x, state, valid=None):
        cfg = self.cfg
        s = x.shape[1]
        di, n, r, k = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                       cfg.mamba_d_conv)
        dt_, f32 = x.dtype, jnp.float32
        xz = _wide(self.in_proj, x)
        xs, z = xz[..., :di], xz[..., di:]
        # the depthwise causal convolution, from the carried window
        # (held in the model's dtype; the chunk's own x is float32)
        xx = jnp.concatenate([state["conv"].astype(f32), xs], axis=1)
        w = self.conv_weight.astype(f32)
        c = sum(xx[:, j:j + s] * w[j] for j in range(k))
        if self.conv_bias is not None:
            c = c + self.conv_bias.astype(f32)
        c = F.silu(c)
        window = _window_after(xx, valid, k - 1)
        dbc = self.x_proj(c.astype(dt_))
        dt = self.dt_layernorm(dbc[..., :r].astype(f32))
        bm = self.b_layernorm(dbc[..., r:r + n].astype(f32))
        cm = self.c_layernorm(dbc[..., r + n:].astype(f32))
        delta = _mask_padding(
            jax.nn.softplus(_wide(self.dt_proj, dt.astype(dt_))), valid)
        a = -jnp.exp(self.A_log.astype(f32))
        y, h = selective_scan(c, delta, a, bm, cm, state["ssm"],
                              form=cfg.scan_form if s > 1 else None)
        y = y + self.D.astype(f32) * c
        y = (y * F.silu(z)).astype(dt_)
        return _wide(self.out_proj, y), {"conv": window.astype(
            state["conv"].dtype), "ssm": h}


class JambaDecoderLayer(Layer):
    def __init__(self, cfg: JambaConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        if kind == "attention":
            self.self_attn = JambaAttention(cfg)
        else:
            self.mamba = JambaMambaMixer(cfg)
        self.pre_ff_layernorm = RMSNorm(cfg.hidden_size,
                                        epsilon=cfg.rms_norm_eps)
        self.feed_forward = LlamaMLP(cfg)
        self.feed_forward.down_proj = _branch_out(cfg, cfg.intermediate_size)

    def forward(self, x, carried=None, valid=None):
        """``carried``: the layer's own piece of what a request carries,
        a ``(k, v, pos)`` cache (attention; None for an uncached
        forward) or a state dict (Mamba).  Returns ``(x, carried')``."""
        # the residual stream ``x`` is float32 whatever the model's
        # dtype; each branch reads it normed, in the weights' dtype
        dt = self.input_layernorm.weight.dtype
        y = self.input_layernorm(x).astype(dt)
        # a branch's scope holds its residual add: a fusion is known by
        # its root (obs/parts.py)
        if self.kind == "attention":
            with jax.named_scope("attention"):
                a, carried = self.self_attn(y, carried)
                x = x + a
        else:
            with jax.named_scope("mixer"):
                a, carried = self.mamba(y, carried, valid)
                x = x + a
        # models/llama.py's gated MLP on its own weights, the gate and
        # the branch's output left float32 (:func:`_wide`)
        ff = self.feed_forward
        y = self.pre_ff_layernorm(x).astype(dt)
        with jax.named_scope("mlp"):
            y = (F.silu(_wide(ff.gate_proj, y))
                 * _wide(ff.up_proj, y)).astype(dt)
            return x + _wide(ff.down_proj, y), carried


class JambaModel(Layer):
    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        # the table is also the head: drawn at the published
        # initializer_range, not at 1, or every position's largest
        # logit is its own input token's by a factor of tens
        self.embed_tokens = Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([JambaDecoderLayer(cfg, kind)
                                 for kind in cfg.layer_kinds])
        self.final_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None, state=None, valid=None):
        """``caches``: None (attention over the chunk alone) or one
        ``(k, v, pos)`` per attention layer; ``state``: one dict per
        Mamba layer (None: zeros, a fresh sequence).  Returns ``(hidden,
        caches', state')``."""
        with jax.named_scope("embed"):
            emb = self.embed_tokens(input_ids)
        if state is None:
            state = _zero_state(self.cfg, input_ids.shape[0], emb.dtype)
        # 56 residual adds in bfloat16 cost 0.044 of the logits' scale
        # against the float32 reference on the chip (PERF.md, PR 32):
        # the stream between layers is float32, as models/ouro.py's
        x = emb.astype(jnp.float32)
        new_caches, new_state = [], []
        for layer in self.layers:
            if layer.kind == "attention":
                cache = None if caches is None \
                    else caches[len(new_caches)]
                x, c = layer(x, cache)
                new_caches.append(c)
            else:
                x, st = layer(x, state[len(new_state)], valid)
                new_state.append(st)
        return (self.final_layernorm(x).astype(emb.dtype), new_caches,
                new_state)


def _state_spec(cfg: JambaConfig, dtype) -> list:
    """Per-slot shapes and dtypes of what the Mamba layers carry."""
    return [{"conv": jax.ShapeDtypeStruct(
                 (cfg.mamba_d_conv - 1, cfg.d_inner), jnp.dtype(dtype)),
             "ssm": jax.ShapeDtypeStruct(
                 (cfg.mamba_d_state, cfg.d_inner), jnp.float32)}
            for _ in range(cfg.num_state_layers)]


def _zero_state(cfg: JambaConfig, batch: int, dtype) -> list:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((batch,) + s.shape, s.dtype),
        _state_spec(cfg, dtype))


class _Carried:
    """``generate``'s view of the model: KV caches and recurrent state
    as ONE cache pytree through ``init_cache`` / ``decode_step``."""

    def __init__(self, model):
        self.model, self.cfg = model, model.cfg

    def init_cache(self, batch: int, max_len: int, dtype=None):
        return (self.model.init_cache(batch, max_len, dtype),
                self.model.init_state(batch, dtype))

    def decode_step(self, input_ids, caches, position):
        kv, state = caches
        logits, kv, state = self.model.decode_step(input_ids, kv, position,
                                                   state=state)
        return logits, (kv, state)


class JambaForCausalLM(Layer):
    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        self.jamba = JambaModel(cfg)

    def _head(self, hidden):
        # tied: the embedding table is the head
        with jax.named_scope("head"):
            return hidden @ self.jamba.embed_tokens.weight.T

    def forward(self, input_ids):
        return self._head(self.jamba(input_ids)[0])

    # ---- what a request carries ----------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None):
        """One ``(k, v, pos)`` plane per ATTENTION layer."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        shape = (batch, max_len, cfg.kv_heads, cfg.head_dim)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                 jnp.asarray(0, jnp.int32))
                for _ in range(cfg.num_cache_layers)]

    def recurrent_state_spec(self, dtype=None):
        """What a serving slot holds BESIDE its KV rows, per slot: a
        pytree of ``jax.ShapeDtypeStruct`` (the engine makes
        ``[num_slots, ...]`` arrays of it and threads them, opaque,
        through ``decode_step(..., state=, valid=)``)."""
        return _state_spec(self.cfg, dtype or self.cfg.dtype)

    def init_state(self, batch: int, dtype=None):
        return _zero_state(self.cfg, batch, dtype or self.cfg.dtype)

    def decode_step(self, input_ids, caches, position, state=None,
                    valid=None):
        """``input_ids [b, s]`` appended at ``position`` (a scalar or
        ``[b]``); ``valid`` the count of real tokens in a right-padded
        chunk (None: all).  Returns ``(logits, caches', state')``."""
        caches = [(k, v, position) for k, v, _ in caches]
        hidden, caches, state = self.jamba(input_ids, caches, state, valid)
        return self._head(hidden), caches, state

    def recurrence_route(self, width: int):
        """``(route, reason)`` of the chunk recurrence at ``width``
        positions (``kernels.selective_scan.scan_route``)."""
        return scan_route(width, self.cfg.d_inner,
                          self.cfg.scan_form if width > 1 else None)

    # ---- what the serving engine may not do with this model ------------
    def serving_refusals(self) -> dict:
        """Engine features that would be WRONG for a model with a
        recurrent state, each with its reason (serving/engine.py raises
        or falls back with it)."""
        return {
            "prefix_cache": (
                "a cached block holds K and V rows but not the recurrent "
                "state at its boundary: a request resumed from it would "
                "run its state-space layers from zeros (needs a state "
                "snapshot per block boundary)"),
            "speculation": (
                "a verify window advances the recurrent state over every "
                "drafted token, and a rejected draft cannot be rolled "
                "back (needs a state checkpoint per window)"),
            "tensor_parallel": (
                "1 KV head cannot partition over the serving mesh's "
                "kv-head axis, and the state-space mixer has no "
                "tensor-parallel layout here"),
        }

    def fused_decode_supported(self, batch: int = 1,
                               kv_len: Optional[int] = None, tp: int = 1):
        return False, ("state-space layers: the fused decode block "
                       "computes attention layers only")

    def generate(self, input_ids, max_new_tokens: int, **kw):
        """Single-scan autoregressive decoding (models/generation.py),
        the recurrent state carried beside the KV caches."""
        if kw.get("prompt_lens") is not None:
            raise ValueError("ragged prompts would advance the recurrent "
                             "state over their padding")
        from .generation import generate
        return generate(_Carried(self), input_ids, max_new_tokens, **kw)


def jamba_tiny(**kw) -> JambaConfig:
    """Both layer kinds (attention at layers 1 and 3 of 4), 1 KV head,
    ``d_conv`` 4."""
    return JambaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=176, num_layers=4,
        num_heads=4, num_kv_heads=1, attn_layer_period=2,
        attn_layer_offset=1, mamba_d_state=8, mamba_d_conv=4,
        mamba_dt_rank=8, max_seq_len=128), **kw})
