"""Jamba family (AI21 Jamba / Jamba2, ``config.json`` and
``modeling_jamba.py`` on Hugging Face): a hybrid decoder, state-space
(Mamba-1) layers with an attention layer every ``attn_layer_period``.

The equations, as this file and ``paddle_tpu/models/jamba.py`` (written
independently of one another) both take them:

* every layer: ``a = x + Mixer(N1(x))``, ``y = a + MLP(N2(a))``, ``N`` an
  RMSNorm, ``MLP(u) = W_down(silu(W_gate u) * W_up u)``; layer ``i``
  mixes by attention iff ``i % attn_layer_period == attn_layer_offset``;
  after the last layer a final RMSNorm and ``logits = h E^T`` with ``E``
  the embedding (tied);
* attention: causal, ``heads`` query heads over ``kv_heads`` K/V heads,
  scale ``1 / sqrt(head_dim)``, no rotary, no positional encoding, no
  bias;
* Mamba, per position ``t`` of the normed input ``u_t``: ``[x_t, z_t] =
  W_in u_t``; ``c_t = silu(b_conv + sum_j w_conv[:, j] x_{t-3+j})``
  (zeros before the sequence); ``[dt_t, B_t, C_t] = W_x c_t``, each
  through its own RMSNorm; ``delta_t = softplus(W_dt dt_t + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t *
  c_t) (x) B_t`` with ``h_{-1} = 0``; ``y_t = h_t C_t + D * c_t``;
  ``out_t = W_out (y_t * silu(z_t))``.

Here the recurrence is a plain ``lax.scan`` over positions whose body
forms ``exp(delta (x) A)`` for ONE position, and attention runs by query
blocks so that 4096 positions fit beside the weights.  Departures from
the published code are under ``assumed`` in the configuration file;
weights are random, drawn by the program's initializers.
"""

import jax
import jax.numpy as jnp

from benchmarks.builders.llama import _rms_norm
from benchmarks.lib.flops_bytes import BYTES

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32",
           "float16": "float16"}

# query rows one block of the reference's attention scores holds
_QUERY_BLOCK = 512


def _kinds(cfg: dict) -> list:
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def model_config(cfg: dict, max_seq_len=None):
    """The program's ``JambaConfig`` for configuration file ``cfg``."""
    from paddle_tpu.models import JambaConfig
    if cfg.get("sliding_window") is not None:
        raise ValueError("the program has no windowed attention")
    if cfg["num_experts"] != 1:
        raise ValueError("the program has no expert layers for this "
                         "family: num_experts must be 1 (every layer's "
                         "feed-forward is the plain gated MLP)")
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the program ties the head to the embedding")
    if cfg["hidden_act"] != "silu":
        raise ValueError("the program's gated MLP is SiLU")
    return JambaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
        mamba_conv_bias=cfg["mamba_conv_bias"],
        mamba_proj_bias=cfg["mamba_proj_bias"],
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], dtype=_DTYPES[cfg["torch_dtype"]])


def model_class():
    from paddle_tpu.models import JambaForCausalLM
    return JambaForCausalLM


def facts(cfg: dict) -> dict:
    """``layers`` counts the layers that hold K and V rows (the
    attention layers: what ``lib/flops_bytes.kv_row_bytes`` multiplies);
    ``matmul_params`` every weight that multiplies activations in a
    matrix product, the tied table counted once, as the head;
    ``stored_params`` what memory holds; ``state_bytes_per_slot`` the
    recurrent state a request carries whatever its length."""
    h, v, m = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    di, n = cfg["mamba_expand"] * h, cfg["mamba_d_state"]
    r, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    kinds = _kinds(cfg)
    n_attn, n_mamba = kinds.count("attention"), kinds.count("mamba")
    proj_bias = (2 * di + h) * bool(cfg["mamba_proj_bias"])
    mamba_matmul = h * 2 * di + di * (r + 2 * n) + r * di + di * h
    mamba_rest = di * k + di * bool(cfg["mamba_conv_bias"]) + di \
        + di * n + di + r + 2 * n + proj_bias      # conv, b_dt, A_log, D, norms
    attn = 2 * h * heads * d + 2 * h * kvh * d
    mlp = 3 * h * m
    dtype = _DTYPES[cfg["torch_dtype"]]
    return {
        "matmul_params": n_mamba * (mamba_matmul + mlp)
        + n_attn * (attn + mlp) + v * h,
        "lookup_params": 0,
        "head_params": v * h,
        "stored_params": n_mamba * (mamba_matmul + mamba_rest + mlp + 2 * h)
        + n_attn * (attn + mlp + 2 * h) + v * h + h,
        "state_bytes_per_slot": n_mamba * (di * n * 4
                                           + di * (k - 1) * BYTES[dtype]),
        "layers": n_attn, "state_layers": n_mamba, "hidden": h,
        "heads": heads, "kv_heads": kvh, "head_dim": d, "vocab": v,
        "dtype": dtype}


def _attention(q, k, v):
    """Causal grouped-query attention, ``q [b, s, kvh, rep, d]``, ``k, v
    [b, s, kvh, d]``, by blocks of query rows."""
    s, d = q.shape[1], q.shape[-1]
    outs = []
    for q0 in range(0, s, _QUERY_BLOCK):
        qb = q[:, q0:q0 + _QUERY_BLOCK]
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) / jnp.sqrt(d)
        qpos = q0 + jnp.arange(qb.shape[1])
        seen = jnp.arange(s)[None, :] <= qpos[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd",
                               jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs, axis=1)


def _mamba(cfg: dict, g, u):
    """The state-space mixer on ``u [b, s, h]`` from a zero state."""
    b, s, h = u.shape
    di, n = cfg["mamba_expand"] * h, cfg["mamba_d_state"]
    r, kw = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    xz = u @ g("in_proj.weight")
    if cfg["mamba_proj_bias"]:
        xz = xz + g("in_proj.bias")
    x, z = xz[..., :di], xz[..., di:]
    # the program holds the convolution's weight [d_conv, d_inner]
    w = g("conv_weight")
    xp = jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0)))
    c = sum(w[j] * xp[:, j:j + s] for j in range(kw))
    if cfg["mamba_conv_bias"]:
        c = c + g("conv_bias")
    c = jax.nn.silu(c)
    dbc = c @ g("x_proj.weight")
    dt = _rms_norm(dbc[..., :r], g("dt_layernorm.weight"), eps)
    bm = _rms_norm(dbc[..., r:r + n], g("b_layernorm.weight"), eps)
    cm = _rms_norm(dbc[..., r + n:], g("c_layernorm.weight"), eps)
    delta = jax.nn.softplus(dt @ g("dt_proj.weight") + g("dt_proj.bias"))
    # the program holds A_log [d_state, d_inner]; here A is [d_inner,
    # d_state] as the equations write it
    a = -jnp.exp(g("A_log")).T

    def step(hs, t):
        d_t, c_t, b_t, c_out = t                     # [b, di] x 2, [b, n] x 2
        hs = jnp.exp(d_t[..., None] * a) * hs \
            + (d_t * c_t)[..., None] * b_t[:, None, :]
        return hs, jnp.einsum("bdn,bn->bd", hs, c_out)

    _, y = jax.lax.scan(
        step, jnp.zeros((b, di, n), jnp.float32),
        [jnp.moveaxis(t, 1, 0) for t in (delta, c, bm, cm)])
    y = jnp.moveaxis(y, 0, 1) + g("D") * c
    out = (y * jax.nn.silu(z)) @ g("out_proj.weight")
    if cfg["mamba_proj_bias"]:
        out = out + g("out_proj.bias")
    return out


def reference_forward(cfg: dict, params: dict, ids):
    """Plain float32 forward, ``ids [b, s] -> logits [b, s, vocab]``: no
    kernels, no cache, no carried state.  ``params`` are the model's
    named parameters (already float32); the caller sets ``highest``
    matmul precision.

    Memory: the caller's float32 copy of 3.03 B parameters is 12.1 GB
    beside the 6.06 GB the model itself holds, more than a 16 GB chip
    has.  The weights were MADE in ``torch_dtype``, so taking them back
    to it loses nothing and undoes the caller's cast (XLA drops the pair
    of converts); each is widened where it is used, one layer at a time
    (the layers depend on one another, so no two layers' float32 copies
    need live together).  All arithmetic is float32."""
    held = jnp.dtype(_DTYPES[cfg["torch_dtype"]])
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    narrow = {k: v.astype(held) for k, v in params.items()}
    b, s = ids.shape
    table = "jamba.embed_tokens.weight"
    x = narrow[table][ids].astype(jnp.float32)
    h = x.shape[-1]
    d = h // heads
    for i, kind in enumerate(_kinds(cfg)):
        def g(name, i=i):
            return narrow[f"jamba.layers.{i}.{name}"].astype(jnp.float32)
        y = _rms_norm(x, g("input_layernorm.weight"), eps)
        if kind == "attention":
            q = (y @ g("self_attn.q_proj.weight")).reshape(
                b, s, kvh, heads // kvh, d)
            k = (y @ g("self_attn.k_proj.weight")).reshape(b, s, kvh, d)
            v = (y @ g("self_attn.v_proj.weight")).reshape(b, s, kvh, d)
            mixed = _attention(q, k, v).reshape(b, s, h) \
                @ g("self_attn.o_proj.weight")
        else:
            mixed = _mamba(cfg, lambda name: g("mamba." + name), y)
        x = x + mixed
        y = _rms_norm(x, g("pre_ff_layernorm.weight"), eps)
        y = jax.nn.silu(y @ g("feed_forward.gate_proj.weight")) \
            * (y @ g("feed_forward.up_proj.weight"))
        x = x + y @ g("feed_forward.down_proj.weight")
    x = _rms_norm(x, narrow["jamba.final_layernorm.weight"]
                  .astype(jnp.float32), eps)
    return x @ narrow[table].astype(jnp.float32).T
