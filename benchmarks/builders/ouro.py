"""Ouro family (ByteDance Ouro-1.4B / 2.6B, ``config.json`` and
``modeling_ouro.py`` on Hugging Face): a looped decoder.  ONE stack of
sandwich-normed layers that every token passes ``total_ut_steps`` times,
the final norm inside each pass, an exit gate after it.

The equations, as this file and ``paddle_tpu/models/ouro.py`` (written
independently of one another) both take them:

* a layer: ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``, four
  RMSNorms; ``Attn`` is causal attention with half-split rotary on q and
  k and no projection bias; ``MLP(u) = W_down(silu(W_gate u) * W_up u)``;
* the stack: ``h_0 = Embed(ids)``; for ``t`` in ``0..T-1``: ``h_{t+1} =
  Norm_f(Layer_{L-1}(... Layer_0(h_t)))``, the same weights every pass;
* the gate: ``lambda_t = sigmoid(w_g . h_{t+1} + b_g)``; pass ``t`` has
  exit mass ``lambda_t * prod_{s<t}(1 - lambda_s)``; a token's output is
  ``h_{t+1}`` of the first pass at which the cumulative mass reaches
  ``early_exit_threshold``, of the last pass where none does; ``logits =
  Head(output)``.  At the published threshold 1 that is the last pass
  unless a gate saturates to exactly 1.

Departures from the published code, all under ``assumed`` in the
configuration file: the order of norm and residual, the absence of
projection biases, the final norm inside each pass and the gate's form
are as remembered from ``modeling_ouro.py``, which is not in this
sandbox; weights are random, drawn by the program's initializers.
"""

import jax
import jax.numpy as jnp

# the same RMSNorm and half-split rotary as the Llama-style reference
from benchmarks.builders.llama import _rms_norm, _rotary

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32",
           "float16": "float16"}


def model_config(cfg: dict, max_seq_len=None):
    """The program's ``OuroConfig`` for configuration file ``cfg``."""
    from paddle_tpu.models import OuroConfig
    if cfg.get("sliding_window") is not None or cfg.get("rope_scaling"):
        raise ValueError("the program has neither windowed attention nor "
                         "rotary scaling for this family")
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("head_dim must be hidden_size / heads here")
    return OuroConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        total_ut_steps=cfg["total_ut_steps"],
        early_exit_threshold=float(cfg["early_exit_threshold"]),
        dtype=_DTYPES[cfg["torch_dtype"]])


def model_class():
    from paddle_tpu.models import OuroForCausalLM
    return OuroForCausalLM


def facts(cfg: dict) -> dict:
    """``layers`` and ``matmul_params`` count what a token passes
    THROUGH: every block weight is applied (and, in a decode step, read
    from HBM: 4.9 GB of blocks do not stay on the chip between passes)
    ``total_ut_steps`` times, and a cached position holds a K and a V row
    per pass per layer.  ``stored_params`` is what memory holds."""
    h, layers, v = (cfg["hidden_size"], cfg["num_hidden_layers"],
                    cfg["vocab_size"])
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, passes = cfg["head_dim"], cfg["total_ut_steps"]
    block = 2 * h * heads * d + 2 * h * kvh * d \
        + 3 * h * cfg["intermediate_size"] + 4 * h
    # per pass: the final norm and the gate; once: the head
    once = v * h
    per_pass = layers * block + h + (h + 1)
    tied = cfg.get("tie_word_embeddings", False)
    lookup = 0 if tied else v * h
    return {"matmul_params": passes * per_pass + once,
            "lookup_params": lookup,
            "stored_params": per_pass + once + lookup,
            "layers": passes * layers, "hidden": h, "heads": heads,
            "kv_heads": kvh, "head_dim": d, "vocab": v,
            "dtype": _DTYPES[cfg["torch_dtype"]]}


_STACK = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
          "down_proj", "input_layernorm", "input_layernorm_2",
          "post_attention_layernorm", "post_attention_layernorm_2")


def reference_forward(cfg: dict, params: dict, ids):
    """Plain float32 forward, ``ids [b, s] -> logits [b, s, vocab]``: no
    kernels, no cache.  ``params`` are the model's named parameters
    (already float32); the caller sets ``highest`` matmul precision.

    Memory: the caller's float32 copy of the 48-layer stack is 9.9 GB
    beside the 5.3 GB the model itself holds, more than a 16 GB chip
    has.  The stack's weights were MADE in ``torch_dtype``, so taking
    them back to it loses nothing and undoes the caller's cast (XLA
    drops the pair of converts); the layer loop then widens ONE layer's
    slice at a time, inside the loop, where the slice depends on the
    loop's counter and cannot be hoisted.  All arithmetic is float32."""
    p = params
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    layers, passes = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    held = jnp.dtype(_DTYPES[cfg["torch_dtype"]])
    stack = {k: p[f"ouro.layers.{k}"].astype(held) for k in _STACK}
    b, s = ids.shape
    h0 = p["ouro.embed_tokens.weight"][ids]
    h = h0.shape[-1]
    d = h // heads
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(l, x):
        g = {k: jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False)
             .astype(jnp.float32) for k, w in stack.items()}
        y = _rms_norm(x, g["input_layernorm"], eps)
        # the program keeps q, k, v as [out, in] (models/ouro.py says
        # why); every other projection is [in, out]
        q = _rotary((y @ g["q_proj"].T).reshape(b, s, heads, d), theta)
        k = _rotary((y @ g["k_proj"].T).reshape(b, s, kvh, d), theta)
        v = (y @ g["v_proj"].T).reshape(b, s, kvh, d)
        # query head j reads kv head j // (heads / kvh)
        q = q.reshape(b, s, kvh, heads // kvh, d)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / jnp.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
        x = x + _rms_norm(att.reshape(b, s, h) @ g["o_proj"],
                          g["input_layernorm_2"], eps)
        y = _rms_norm(x, g["post_attention_layernorm"], eps)
        y = (jax.nn.silu(y @ g["gate_proj"]) * (y @ g["up_proj"])) \
            @ g["down_proj"]
        return x + _rms_norm(y, g["post_attention_layernorm_2"], eps)

    threshold = float(cfg["early_exit_threshold"])
    x, out = h0, None
    survive = jnp.ones((b, s), jnp.float32)     # prod(1 - lambda) so far
    left = jnp.zeros((b, s), bool)
    for t in range(passes):
        x = jax.lax.fori_loop(0, layers, layer, x)
        x = _rms_norm(x, p["ouro.norm.weight"], eps)
        lam = jax.nn.sigmoid(
            (x @ p["ouro.early_exit_gate.weight"])[..., 0]
            + p["ouro.early_exit_gate.bias"][0])
        survive = survive * (1.0 - lam)
        # cumulative exit mass 1 - survive >= threshold
        leaves = ~left & ((survive <= 1.0 - threshold) | (t == passes - 1))
        out = x if out is None else jnp.where(leaves[..., None], x, out)
        left = left | leaves
    if cfg.get("tie_word_embeddings", False):
        return out @ p["ouro.embed_tokens.weight"].T
    return out @ p["lm_head.weight"]
