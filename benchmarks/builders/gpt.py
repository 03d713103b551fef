"""GPT family (Brown et al. 2020): pre-LN decoder, learned positions,
fused QKV with biases, tanh-GELU MLP, head tied to the token table."""

import jax
import jax.numpy as jnp


def model_config(cfg: dict, max_seq_len=None):
    """The program's ``GPTConfig`` for configuration file ``cfg``."""
    from paddle_tpu.models import GPTConfig
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        max_seq_len=max_seq_len or cfg["max_seq_len"],
        ffn_mult=cfg["ffn_mult"], dtype=cfg["dtype"],
        layer_norm_eps=cfg["layer_norm_eps"],
        tie_embeddings=cfg["tie_embeddings"], use_bias=cfg["use_bias"],
        remat=cfg.get("remat", True))


def model_class():
    from paddle_tpu.models import GPTForCausalLM
    return GPTForCausalLM


def facts(cfg: dict) -> dict:
    h, layers, v = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    ffn = h * cfg["ffn_mult"]
    block = 4 * h * h + 2 * h * ffn
    if cfg["use_bias"]:
        # biases: qkv 3h, out h, fc_in ffn, fc_out h; two LayerNorms 4h
        block += 9 * h + ffn
    else:
        block += 4 * h
    # the tied table is the head's matmul; as an embedding it is a lookup
    head = v * h
    lookup = cfg["max_seq_len"] * h + (0 if cfg["tie_embeddings"] else v * h)
    return {"matmul_params": layers * block + head + 2 * h,
            "lookup_params": lookup, "layers": layers, "hidden": h,
            "heads": cfg["num_heads"], "kv_heads": cfg["num_heads"],
            "head_dim": h // cfg["num_heads"], "vocab": v,
            "dtype": cfg["dtype"]}


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def reference_forward(cfg: dict, params: dict, ids):
    """Plain float32 forward, ``ids [b, s] -> logits [b, s, vocab]``: no
    kernels, no cache.  ``params`` are the model's named parameters
    (already float32); the caller sets ``highest`` matmul precision."""
    p = params
    heads, eps = cfg["num_heads"], cfg["layer_norm_eps"]
    b, s = ids.shape
    x = p["gpt.wte.weight"][ids] + p["gpt.wpe.weight"][jnp.arange(s)]
    h = x.shape[-1]
    d = h // heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg["num_layers"]):
        g = lambda name: p[f"gpt.h.{i}.{name}"]
        bias = (lambda name: p[f"gpt.h.{i}.{name}"]) if cfg["use_bias"] \
            else (lambda name: 0.0)
        y = _layer_norm(x, g("ln_1.weight"), g("ln_1.bias"), eps)
        qkv = (y @ g("qkv.weight") + bias("qkv.bias")) \
            .reshape(b, s, 3, heads, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + att.reshape(b, s, h) @ g("out_proj.weight") \
            + bias("out_proj.bias")
        y = _layer_norm(x, g("ln_2.weight"), g("ln_2.bias"), eps)
        y = jax.nn.gelu(y @ g("fc_in.weight") + bias("fc_in.bias"),
                        approximate=True)
        x = x + y @ g("fc_out.weight") + bias("fc_out.bias")
    x = _layer_norm(x, p["gpt.ln_f.weight"], p["gpt.ln_f.bias"], eps)
    if cfg["tie_embeddings"]:
        return x @ p["gpt.wte.weight"].T
    return x @ p["lm_head.weight"]
