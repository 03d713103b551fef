"""DeepSeek-V3-shaped family (arXiv:2412.19437; ``config.json`` keys of
``model_type`` ``deepseek_v3`` and of JoyAI-LLM-Flash's
``joyai_llm_flash``, which are the same keys): latent attention (MLA)
and sparse experts beside a shared one.

The equations, as this file and ``paddle_tpu/models/deepseek_v3.py``
(written independently of one another) both take them:

* every layer: ``a = x + Attn(N1(x))``, ``y = a + FFN(N2(a))``, ``N`` an
  RMSNorm; after the last layer a final RMSNorm and ``logits = h
  W_head`` (untied).  The first ``first_k_dense_replace`` layers:
  ``FFN(u) = W_down(silu(W_gate u) * W_up u)`` at ``intermediate_size``;
* latent attention, per position ``t`` of the normed input ``u_t``:
  ``cq = N_q(W_dq u_t)``; ``[qn_i, qr_i] = W_uq cq`` per head ``i``
  (``nope + rope``); ``[ckv_t, kr_t] = W_dkv u_t`` (``kv_lora_rank +
  rope``); ``c_t = N_kv(ckv_t)``; ``[kn_ti, v_ti] = W_ukv c_t`` per head;
  ``q_i = [qn_i, R_t(qr_i)]``, ``k_ti = [kn_ti, R_t(kr_t)]`` with ``R``
  rotating the pairs ``(2j, 2j+1)`` by ``t / theta^(2j / rope)``, the
  one rotary key shared by all heads; causal softmax of ``q_i . k_si /
  sqrt(nope + rope)``; ``o_i = sum_s p_s v_si``; ``out = W_o [o_1 ..]``;
* expert layer, per token: ``s = sigmoid(W_g u)`` in float32; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` are chosen
  (``b`` the ``e_score_correction_bias``: the choice only); ``w_e =
  routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``;
  ``FFN(u) = sum over the chosen of w_e E_e(u) + E_shared(u)``, each
  ``E`` a SiLU-gated MLP of ``moe_intermediate_size``.

Here attention is EXPANDED (K and V per head from ``c``, no absorption,
no cache) by query blocks, and the expert layer is the sum over ALL
experts with ``w_e = 0`` off the chosen (no sort, no groups, nothing
that could drop a token), a block of experts at a time.  The
multi-token-prediction module takes no part in the published inference
forward (``num_nextn_predict_layers`` must be 0 in the configuration
file; it is listed under ``reduced``).
"""

import jax
import jax.numpy as jnp

from benchmarks.builders.llama import _rms_norm

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32",
           "float16": "float16"}

# query rows one block of the reference's attention scores holds
_QUERY_BLOCK = 512
# experts whose float32 weights one step of the reference's expert sum
# holds (16 x 3 x 2048 x 768 x 4 B = 302 MB at the published widths)
_EXPERT_BLOCK = 16
# vocabulary columns one block of the reference's head holds in float32
_VOCAB_BLOCKS = 8


def model_config(cfg: dict, max_seq_len=None):
    """The program's ``DeepseekV3Config`` for configuration file
    ``cfg``."""
    from paddle_tpu.models import DeepseekV3Config
    refuse = {
        "hidden_act": "silu", "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "moe_layer_freq": 1,
        "attention_bias": False, "tie_word_embeddings": False,
        "rope_scaling": None, "rope_interleave": True,
        "num_nextn_predict_layers": 0, "ep_size": 1}
    for key, want in refuse.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} {cfg[key]!r}: the program "
                             f"implements {want!r} only")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not nope + rope")
    # n_group / topk_group other than 1 / 1 are refused by the config
    # the stds the weights are drawn at (``assumed.initializer`` of the
    # configuration file says why): config.json gives none
    init = cfg.get("initializer", {})
    return DeepseekV3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        **{key: init[name] for key, name in (
            ("initializer_range", "range"),
            ("routed_out_range", "routed_out")) if name in init},
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        dtype=_DTYPES[cfg["torch_dtype"]])


def model_class():
    from paddle_tpu.models import DeepseekV3ForCausalLM
    return DeepseekV3ForCausalLM


def facts(cfg: dict) -> dict:
    """``dense_params``: every weight a step multiplies whatever the
    routing (attention, the dense layers' MLP, the shared experts, the
    routers, the head); ``expert_params`` ONE routed expert's;
    ``matmul_params`` all of them with every routed expert (what memory
    holds, not what a step reads: ``lib/moe_flops_bytes.py`` counts the
    experts a program touched).  ``layers`` x ``kv_heads`` x ``head_dim``
    x 2 is the accepted ``kv_row_bytes``' product: the latent row as
    held (``cache_row_width``), written as two halves."""
    h, v, layers = (cfg["hidden_size"], cfg["vocab_size"],
                    cfg["num_hidden_layers"])
    heads = cfg["num_attention_heads"]
    lora, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    attn = h * cfg["q_lora_rank"] \
        + cfg["q_lora_rank"] * heads * (nope + rope) + h * (lora + rope) \
        + lora * heads * (nope + vd) + heads * vd * h
    attn_norms = cfg["q_lora_rank"] + lora
    expert = 3 * h * cfg["moe_intermediate_size"]
    dense_layers = min(cfg["first_k_dense_replace"], layers)
    expert_layers = layers - dense_layers
    experts = cfg["n_routed_experts"]
    router = h * experts
    dense = layers * attn + dense_layers * 3 * h * cfg["intermediate_size"] \
        + expert_layers * (cfg["n_shared_experts"] * expert + router) \
        + v * h
    dtype = _DTYPES[cfg["torch_dtype"]]
    # the row as the cache HOLDS it: in whole 128-lane tiles (576 ->
    # 640), which is what the device's tiled layout takes for it
    held = -(-(lora + rope) // 128) * 128
    return {
        "dense_params": dense, "expert_params": expert,
        "expert_layers": expert_layers, "experts": experts,
        "experts_per_token": cfg["num_experts_per_tok"],
        "matmul_params": dense + expert_layers * experts * expert,
        "lookup_params": v * h, "head_params": v * h,
        "stored_params": dense + expert_layers * experts * (expert + 1)
        + v * h + layers * (attn_norms + 2 * h) + h,
        "latent_row_width": lora + rope, "cache_row_width": held,
        "kv_lora_rank": lora,
        "qk_head_dim": nope + rope, "v_head_dim": vd,
        "layers": layers, "hidden": h, "heads": heads, "kv_heads": 1,
        "head_dim": held // 2, "vocab": v, "dtype": dtype,
        # how the device trace names the grouped matmul over the experts
        # (the Pallas kernel's call, jax's megablox.gmm)
        "expert_matmul_ops": ("gmm",),
    }


def _rotate_pairs(x, positions, theta):
    """``R_t``: the pairs ``(2j, 2j+1)`` of the last axis of ``x [b, s,
    ..., d]`` turned by ``t / theta^(2j / d)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv          # [s, d/2]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(cfg: dict, g, u):
    """Expanded latent attention on ``u [b, s, h]``, no cache."""
    b, s, _ = u.shape
    heads, lora = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    positions = jnp.arange(s)
    cq = _rms_norm(u @ g("q_a_proj.weight"), g("q_a_layernorm.weight"), eps)
    q = (cq @ g("q_b_proj.weight")).reshape(b, s, heads, nope + rope)
    ckv = u @ g("kv_a_proj_with_mqa.weight")
    c = _rms_norm(ckv[..., :lora], g("kv_a_layernorm.weight"), eps)
    kr = _rotate_pairs(ckv[..., lora:], positions, theta)       # [b, s, rope]
    kv = (c @ g("kv_b_proj.weight")).reshape(b, s, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kr[:, :, None, :], (b, s, heads, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], _rotate_pairs(q[..., nope:], positions, theta)], -1)
    outs = []
    for q0 in range(0, s, _QUERY_BLOCK):
        qb = q[:, q0:q0 + _QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) \
            / jnp.sqrt(float(nope + rope))
        qpos = q0 + jnp.arange(qb.shape[1])
        scores = jnp.where(positions[None, :] <= qpos[:, None], scores,
                           -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, -1), v))
    o = jnp.concatenate(outs, axis=1).reshape(b, s, heads * vd)
    return o @ g("o_proj.weight")


def _expert_weights(cfg: dict, scores, bias):
    """``w [t, experts]``: the published weight on the chosen, 0 off
    them.  The chosen are those whose ``s + b`` reaches the k-th
    largest."""
    k = cfg["num_experts_per_tok"]
    choice = scores + bias
    kth = jnp.sort(choice, axis=-1)[:, -k][:, None]
    w = jnp.where(choice >= kth, scores, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def _experts(cfg: dict, narrow, prefix: str, u):
    """The expert layer on ``u [t, h]``: all experts, ``_EXPERT_BLOCK``
    at a time, each block's weights widened where it is used."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(u @ narrow[prefix + "gate.weight"].astype(f32))
    w = _expert_weights(
        cfg, scores, narrow[prefix + "e_score_correction_bias"].astype(f32))
    experts = scores.shape[-1]
    block = min(_EXPERT_BLOCK, experts)
    if experts % block:
        raise ValueError(f"{experts} experts in blocks of {block}")

    def blocks(a):
        return a.reshape((experts // block, block) + a.shape[1:])

    def step(acc, xs):
        wg, wu, wd, wt = xs                     # [block, ...], wt [block, t]
        a = jax.nn.silu(jnp.einsum("th,ehm->etm", u, wg.astype(f32))) \
            * jnp.einsum("th,ehm->etm", u, wu.astype(f32))
        y = jnp.einsum("etm,emh->eth", a, wd.astype(f32))
        return acc + jnp.einsum("eth,et->th", y, wt), None

    out, _ = jax.lax.scan(
        step, jnp.zeros_like(u),
        (blocks(narrow[prefix + "gate_proj"]),
         blocks(narrow[prefix + "up_proj"]),
         blocks(narrow[prefix + "down_proj"]), blocks(w.T)))
    if cfg["n_shared_experts"]:
        def g(name):
            return narrow[prefix + name].astype(f32)
        out = out + (jax.nn.silu(u @ g("shared_experts.gate_proj.weight"))
                     * (u @ g("shared_experts.up_proj.weight"))) \
            @ g("shared_experts.down_proj.weight")
    return out, w


def reference_forward(cfg: dict, params: dict, ids, with_routing=False):
    """Plain float32 forward, ``ids [b, s] -> logits [b, s, vocab]``: no
    kernels, no cache, no absorption, no sort.  ``params`` are the
    model's named parameters (already float32); the caller sets
    ``highest`` matmul precision.  ``with_routing``: also the expert
    weights ``[expert layers, b * s, experts]`` (0 off the chosen).

    Memory: the caller's float32 copy of 5.56 B parameters is 22 GB.
    The weights were MADE in ``torch_dtype``, so taking them back to it
    loses nothing and undoes the caller's cast (XLA drops the pair of
    converts); each is widened where it is used: a layer's attention at
    a time, ``_EXPERT_BLOCK`` experts at a time, the head by
    ``_VOCAB_BLOCKS`` column blocks.  All arithmetic is float32."""
    f32 = jnp.float32
    held = jnp.dtype(_DTYPES[cfg["torch_dtype"]])
    eps = cfg["rms_norm_eps"]
    narrow = {k: v.astype(held) for k, v in params.items()}
    b, s = ids.shape
    x = narrow["model.embed_tokens.weight"][ids].astype(f32)
    h = x.shape[-1]
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        prefix = f"model.layers.{i}."

        def g(name, prefix=prefix):
            return narrow[prefix + name].astype(f32)

        u = _rms_norm(x, g("input_layernorm.weight"), eps)
        x = x + _attention(cfg, lambda n: g("self_attn." + n), u)
        u = _rms_norm(x, g("post_attention_layernorm.weight"), eps)
        if i < cfg["first_k_dense_replace"]:
            y = (jax.nn.silu(u @ g("mlp.gate_proj.weight"))
                 * (u @ g("mlp.up_proj.weight"))) @ g("mlp.down_proj.weight")
        else:
            y, w = _experts(cfg, narrow, prefix + "mlp.",
                            u.reshape(b * s, h))
            y = y.reshape(b, s, h)
            routing.append(w)
        x = x + y
    x = _rms_norm(x, narrow["model.norm.weight"].astype(f32), eps)
    head = narrow["lm_head.weight"]
    edges = [head.shape[1] * j // _VOCAB_BLOCKS
             for j in range(_VOCAB_BLOCKS + 1)]
    logits = jnp.concatenate(
        [x @ head[:, lo:hi].astype(f32)
         for lo, hi in zip(edges, edges[1:])], axis=-1)
    return (logits, jnp.stack(routing)) if with_routing else logits
