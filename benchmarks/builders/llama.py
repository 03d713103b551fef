"""Llama-style family (RMSNorm, rotary positions, grouped-query
attention, SwiGLU, untied head), read from a Hugging Face ``config.json``
as published: Mistral-7B (arXiv:2310.06825) runs through it at its own
sizes.  Sliding-window attention is NOT implemented by the program; a
configuration that declares a window may only be run at sequence lengths
inside it, where causal attention is the same function (``assumed`` in
the configuration file says so, and ``model_config`` refuses otherwise).
"""

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32",
           "float16": "float16"}


def model_config(cfg: dict, max_seq_len=None):
    """The program's ``LlamaConfig`` for configuration file ``cfg``."""
    from paddle_tpu.models import LlamaConfig
    seq = max_seq_len or cfg["max_position_embeddings"]
    window = cfg.get("sliding_window")
    if window is not None and seq > window:
        raise ValueError(
            f"sequence length {seq} exceeds sliding_window {window}: the "
            f"program has no windowed attention, so the run would not be "
            f"the published model")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], max_seq_len=seq,
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        dtype=_DTYPES[cfg["torch_dtype"]], remat=cfg.get("remat", False))


def model_class():
    from paddle_tpu.models import LlamaForCausalLM
    return LlamaForCausalLM


def facts(cfg: dict) -> dict:
    h, layers, v = (cfg["hidden_size"], cfg["num_hidden_layers"],
                    cfg["vocab_size"])
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    block = 2 * h * h + 2 * h * kvh * d + 3 * h * cfg["intermediate_size"] \
        + 2 * h
    tied = cfg.get("tie_word_embeddings", False)
    return {"matmul_params": layers * block + v * h + h,
            "lookup_params": 0 if tied else v * h,
            "layers": layers, "hidden": h, "heads": heads, "kv_heads": kvh,
            "head_dim": d, "vocab": v, "dtype": _DTYPES[cfg["torch_dtype"]]}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, theta):
    """Rotate pairs (x[i], x[i + d/2]) by position * theta**(-2i/d): the
    half-split pairing of the published implementation."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_forward(cfg: dict, params: dict, ids):
    """Plain float32 forward, ``ids [b, s] -> logits [b, s, vocab]``: no
    kernels, no cache.  ``params`` are the model's named parameters
    (already float32); the caller sets ``highest`` matmul precision."""
    p = params
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s = ids.shape
    x = p["llama.embed_tokens.weight"][ids]
    h = x.shape[-1]
    d = h // heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg["num_hidden_layers"]):
        g = lambda name: p[f"llama.layers.{i}.{name}"]
        y = _rms_norm(x, g("input_layernorm.weight"), eps)
        q = (y @ g("self_attn.q_proj.weight")).reshape(b, s, heads, d)
        k = (y @ g("self_attn.k_proj.weight")).reshape(b, s, kvh, d)
        v = (y @ g("self_attn.v_proj.weight")).reshape(b, s, kvh, d)
        q, k = _rotary(q, theta), _rotary(k, theta)
        # query head j reads kv head j // (heads / kvh)
        q = q.reshape(b, s, kvh, heads // kvh, d)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / jnp.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
        x = x + att.reshape(b, s, h) @ g("self_attn.o_proj.weight")
        y = _rms_norm(x, g("post_attention_layernorm.weight"), eps)
        y = jax.nn.silu(y @ g("mlp.gate_proj.weight")) \
            * (y @ g("mlp.up_proj.weight"))
        x = x + y @ g("mlp.down_proj.weight")
    x = _rms_norm(x, p["llama.norm.weight"], eps)
    if cfg.get("tie_word_embeddings", False):
        return x @ p["llama.embed_tokens.weight"].T
    return x @ p["lm_head.weight"]
