"""Ouro — a looped decoder: ONE stack of layers that every token passes
``total_ut_steps`` times (ByteDance Ouro-1.4B/2.6B, ``modeling_ouro.py``).

What differs from the Llama-style decoder (models/llama.py), whose
rotary, RMSNorm, SwiGLU and decode-attention pieces this file reuses:

* the layer is sandwich-normed, four RMSNorms a layer:
  ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``;
* the stack runs ``T = total_ut_steps`` times over the same weights,
  ``h_{t+1} = Norm_f(Layers(h_t))``, the normed output feeding the next
  pass, and attention in pass ``t``, layer ``l`` reads and writes its OWN
  KV plane ``t * num_layers + l``: ``T * num_layers`` planes;
* an exit gate ``lambda_t = sigmoid(Linear(h -> 1)(h_{t+1}))`` gives
  pass ``t`` the exit mass ``lambda_t * prod_{s<t}(1 - lambda_s)``; a
  token's output is the first pass at which the cumulative mass reaches
  ``early_exit_threshold``, else the last.  Every pass is always
  computed (later tokens attend to every plane), so the gate chooses an
  output and saves nothing; at the published threshold 1 it chooses the
  last pass.

TPU-native: both loops are ``lax.scan`` in the program, the passes over
the layers and the layers over weights stacked on a leading axis, so a
program holds ONE traced layer body whatever ``T * num_layers`` is (192
for Ouro-2.6B; unrolled, its decode program would hold 192 Pallas
attention calls).  A loop in the program can only reach a plane by a
traced index, so the planes of one cache live in ONE slab, side by side
on the head axis: ``[batch, max_len, planes * kv_heads, head_dim]``,
plane ``p`` at heads ``[p * kv_heads, (p + 1) * kv_heads)``.  The slab
rides the scans' carry, is appended to in place, and is attended to in
place: the decode-attention kernel windows plane ``p`` by its first
head (``head0``, kernels/decode_attention.py).  To the serving
engine it is an ordinary cache slab with many heads
(``cfg.cache_planes_per_slab`` tells the pools how many planes one slab
holds; serving/kv_pool.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.norm import RMSNorm
from ..kernels.decode_attention import append_and_attend
from .llama import _rope_tables, apply_rotary_pos_emb

__all__ = ["OuroConfig", "OuroStack", "OuroModel", "OuroForCausalLM",
           "ouro_tiny"]


@dataclasses.dataclass
class OuroConfig:
    """Defaults are ByteDance/Ouro-2.6B's ``config.json`` (float32 until
    a caller names the serving dtype)."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: Optional[int] = None      # None -> MHA
    max_seq_len: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    dtype: str = "float32"

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def loop_passes(self) -> int:
        """Times a token passes the layer stack in one forward step."""
        return self.total_ut_steps

    @property
    def num_cache_layers(self) -> int:
        """KV planes one cached position spans: a plane per pass per
        layer (the serving pools size themselves from this)."""
        return self.total_ut_steps * self.num_layers

    @property
    def cache_planes_per_slab(self) -> int:
        """Planes one cache slab holds on its head axis: all of them,
        because the scans reach a plane by a traced index."""
        return self.num_cache_layers

    def num_params(self) -> int:
        h, v = self.hidden_size, self.vocab_size
        kvh = self.kv_heads * self.head_dim
        block = 2 * h * h + 2 * h * kvh + 3 * h * self.intermediate_size \
            + 4 * h
        return self.num_layers * block + 2 * v * h + h + (h + 1)


def plane_index(t, layer, num_layers: int):
    """The KV plane of pass ``t``, layer ``layer``."""
    return t * num_layers + layer


_NORMS = ("input_layernorm", "input_layernorm_2",
          "post_attention_layernorm", "post_attention_layernorm_2")


class OuroStack(Layer):
    """The decoder layers' weights, each kind stacked on a leading layer
    axis: what ``lax.scan`` slices a layer from.  Linear weights are
    ``[num_layers, in, out]`` and drawn as ``nn.Linear`` draws them
    (Xavier normal per layer), input norms' weights are ones, output
    norms' ``1 / sqrt(2 * num_layers)`` (below).  ``q_proj``,
    ``k_proj`` and ``v_proj`` alone are held ``[num_layers, out, in]``:
    that is the layout XLA:TPU gives those three operands, and held
    ``[in, out]`` every decode and prefill program transposed all three
    stacks (1.2 GB at Ouro-2.6B) ahead of its loop, on every call
    (compile for the chip, PR 28: ``temp`` 1.21 GB against 0.001)."""

    def __init__(self, cfg: OuroConfig):
        super().__init__()
        n, h, d = cfg.num_layers, cfg.hidden_size, cfg.head_dim
        m = cfg.intermediate_size

        def linear(fan_in, fan_out):
            return self.create_parameter(
                (n, fan_in, fan_out), default_initializer=I.Normal(
                    0.0, math.sqrt(2.0 / (fan_in + fan_out))))

        self.q_proj = linear(cfg.num_heads * d, h)
        self.k_proj = linear(cfg.kv_heads * d, h)
        self.v_proj = linear(cfg.kv_heads * d, h)
        self.o_proj = linear(cfg.num_heads * d, h)
        self.gate_proj = linear(h, m)
        self.up_proj = linear(h, m)
        self.down_proj = linear(m, h)
        # the two norms on a branch's OUTPUT start at 1 / sqrt(2 *
        # layers), GPT-2's scaling of residual branches: a pass's 2 *
        # layers branches then add up to about the scale of the stream
        # they join.  At 1 a randomly drawn looped stack is chaotic: each
        # pass re-enters its own normed output, and bf16 rounding grew
        # to 0.16 of the logits over 4 x 48 applications on the chip
        # while the float32 system sat within 6e-6 (PERF.md, PR 28)
        branch = 1.0 / math.sqrt(2.0 * n)
        for name in _NORMS:
            setattr(self, name, self.create_parameter(
                (n, h), default_initializer=I.Constant(
                    branch if name.endswith("_2") else 1.0)))

    def weights(self) -> dict:
        return dict(self._parameters)


def _norm(x, w, eps):
    """RMSNorm in float32 (the residual stream's type)."""
    return F.rms_norm(x.astype(jnp.float32), w, None, eps)


class OuroModel(Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = OuroStack(cfg)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.early_exit_gate = Linear(cfg.hidden_size, 1)

    def _qkv(self, w, x, cos, sin):
        """First half of one sandwich-normed layer: ``N1`` and the
        rotated projections of the float32 residual stream ``x [b, s,
        h]``; ``w`` is the layer's slice of the stack."""
        cfg = self.cfg
        d = cfg.head_dim
        b, s, _ = x.shape
        dt = w["q_proj"].dtype
        y = _norm(x, w["input_layernorm"], cfg.rms_norm_eps).astype(dt)

        def heads(name, n):         # these three are held [out, in]
            return jnp.einsum("bsh,oh->bso", y, w[name]).reshape(b, s, n, d)

        with jax.named_scope("attention"):
            q = heads("q_proj", cfg.num_heads)
            k = heads("k_proj", cfg.kv_heads)
            v = heads("v_proj", cfg.kv_heads)
            return (apply_rotary_pos_emb(q, cos, sin).astype(dt),
                    apply_rotary_pos_emb(k, cos, sin).astype(dt), v)

    def _finish(self, w, x, a):
        """Second half: ``x + N2(o_proj(a))``, then the SwiGLU branch
        between ``N3`` and ``N4``; ``a [b, s, heads, d]`` is the
        attention's output."""
        eps = self.cfg.rms_norm_eps
        b, s, _ = x.shape
        dt = w["q_proj"].dtype
        # a branch's scope holds its residual add (a fusion is known by
        # its root, obs/parts.py); the norms inside name themselves
        with jax.named_scope("attention"):
            x = x + _norm(a.reshape(b, s, -1) @ w["o_proj"],
                          w["input_layernorm_2"], eps)
        y = _norm(x, w["post_attention_layernorm"], eps).astype(dt)
        with jax.named_scope("mlp"):
            y = (F.silu(y @ w["gate_proj"]) * (y @ w["up_proj"])) \
                @ w["down_proj"]
            return x + _norm(y, w["post_attention_layernorm_2"], eps)

    def forward(self, input_ids, caches=None, position_offset=0):
        """``caches``: None (a full causal forward), or the one-slab
        cache ``[(k, v, pos)]`` of :meth:`OuroForCausalLM.init_cache`.
        Returns the chosen pass's normed hidden states ``[b, s, h]`` in
        the model's dtype (and the new caches)."""
        cfg = self.cfg
        n, passes, kvh = cfg.num_layers, cfg.total_ut_steps, cfg.kv_heads
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            emb = self.embed_tokens(input_ids)
        with jax.named_scope("attention"):
            pos = jnp.asarray(position_offset)[..., None] + jnp.arange(s)
            cos, sin = _rope_tables(pos, cfg.head_dim, cfg.rope_theta,
                                    jnp.float32)
        stack = self.layers.weights()
        slabs = None
        if caches is not None:
            (pk, pv, cpos), = caches
            slabs = (pk, pv)

        def layer(t):
            def body(carry, xs):
                x, slabs = carry
                w, l = xs
                q, k, v = self._qkv(w, x, cos, sin)
                with jax.named_scope("attention"):
                    if slabs is None:
                        a = F.scaled_dot_product_attention(
                            q, k, v, is_causal=True, training=False)
                    else:
                        # the plane is a window of the slab's head axis:
                        # the kernel writes and reads it where it lies
                        a, ks, vs = append_and_attend(
                            q, k, v, *slabs, cpos, kv_heads=kvh,
                            head0=plane_index(t, l, n) * kvh)
                        slabs = (ks, vs)
                return (self._finish(w, x, a), slabs), None
            return body

        gate_w = self.early_exit_gate.weight.astype(jnp.float32)
        gate_b = self.early_exit_gate.bias.astype(jnp.float32)
        # a token leaves at the first pass whose cumulative exit mass
        # reaches the threshold: 1 - prod(1 - lambda) >= threshold, held
        # as prod <= 1 - threshold so that at threshold 1 only a gate
        # that saturates to exactly 1 leaves early
        survive_bar = 1.0 - cfg.early_exit_threshold

        def one_pass(carry, t):
            x, slabs, out, survive, done = carry
            (x, slabs), _ = jax.lax.scan(
                layer(t), (x, slabs), (stack, jnp.arange(n)))
            h = _norm(x, self.norm.weight, cfg.rms_norm_eps)
            with jax.named_scope("exit_gate"):
                lam = jax.nn.sigmoid((h @ gate_w)[..., 0] + gate_b[0])
                survive = survive * (1.0 - lam)
                take = ~done & ((survive <= survive_bar)
                                | (t == passes - 1))
                out = jnp.where(take[..., None], h, out)
            return (h, slabs, out, survive, done | take), None

        x0 = emb.astype(jnp.float32)
        (_, slabs, out, _, _), _ = jax.lax.scan(
            one_pass,
            (x0, slabs, jnp.zeros_like(x0), jnp.ones((b, s), jnp.float32),
             jnp.zeros((b, s), bool)),
            jnp.arange(passes))
        out = out.astype(emb.dtype)
        if caches is None:
            return out
        return out, [(slabs[0], slabs[1], cpos + s)]


class OuroForCausalLM(Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        self.ouro = OuroModel(cfg)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                              bias_attr=False)

    def _head(self, hidden):
        with jax.named_scope("head"):
            return self.lm_head(hidden)

    def forward(self, input_ids):
        return self._head(self.ouro(input_ids))

    # ---- incremental decode -------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None):
        """ONE slab for K and one for V, every plane side by side on the
        head axis (see the module docstring)."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        shape = (batch, max_len, cfg.num_cache_layers * cfg.kv_heads,
                 cfg.head_dim)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                 jnp.asarray(0, jnp.int32))]

    def decode_step(self, input_ids, caches, position):
        hidden, new_caches = self.ouro(input_ids, caches,
                                       position_offset=position)
        return self._head(hidden), new_caches

    def fused_decode_supported(self, batch: int = 1,
                               kv_len: Optional[int] = None, tp: int = 1):
        """The fused decode-block kernels compute a pre-norm layer; this
        layer norms each branch's OUTPUT too.  ``(False, reason)``."""
        return False, ("sandwich-normed layer (a norm after the "
                       "attention and the MLP output): the fused decode "
                       "block computes pre-norm layers only")

    def generate(self, input_ids, max_new_tokens: int, **kw):
        """Single-scan autoregressive decoding (models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, **kw)


def ouro_tiny(**kw) -> OuroConfig:
    return OuroConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=176, num_layers=3,
        num_heads=4, max_seq_len=128, total_ut_steps=3), **kw})
