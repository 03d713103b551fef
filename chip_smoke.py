"""chip_smoke.py: the quickest proof that paddle_tpu still starts on the chip.

Drives the system's main path once through the entry points a user calls,
at GPT-3 6.7B's published widths (``models.gpt3_6_7b``: h4096, 32x128
heads, ffn 16384, vocab 50304, s2048, bf16) with depth cut to what one
16 GB v5e chip holds and random weights from a seed:

  * train      GPTForCausalLM + AdamW(multi_precision) in one donated jit
               step; loss finite and falling, flash attention compiled by
               Mosaic;
  * kernels    every Pallas kernel the other phases do not judge through
               logits, against its float32 reference;
  * serve      ServingEngine through submit()/step()/result(): mixed
               prompt lengths, a second wave sharing a cached prefix;
               every token the engine emits must be a near-argmax of the
               model's full f32 forward, prefill-then-decode logits must
               match it, the compiled-program counts must sit at their
               pins;
  * serve fused  the same with ``fused_decode=True`` on the SAME model
               instance, plus fused-vs-unfused decode logits;
  * four chips (when ``jax.device_count() >= 4``) tensor_parallel=4
               serving, composed and fused, against tp=1, and
               GPTHybridTrainer over the four devices.

One process, one touch of JAX, no child that needs the chip.  A phase
that fails ends the run there with its traceback and a non-zero code;
nothing is caught and carried on.  ``main()`` is the only place that
fixes the full width and demands a TPU: tests/test_chip_smoke.py calls
the same phase functions at ``gpt_tiny`` size on the CPU.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.metadata
import json
import os
import sys
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

# bf16 keeps 8 significant bits, so one rounding is 2**-9 relative; the
# logits here are sums over thousands of such terms through every layer.
# Agreement is judged on max|system - reference| / max|reference| of a
# logit vector: measured 0.0057-0.0074 on v5e at full width, four layers
# (my chip runs, PR 21, in CHANGES.md).  Four times that is the bar: a
# format with 5 significant bits instead of 8 would sit near 0.05, a
# wrong mask or position near 1.
BF16_LOGIT_TOL = 0.03
# float32 systems (the CPU rehearsal) differ from the reference only by
# summation order
F32_LOGIT_TOL = 2e-4


def logit_tol(dtype) -> float:
    return BF16_LOGIT_TOL if jnp.dtype(dtype) == jnp.bfloat16 \
        else F32_LOGIT_TOL


# ------------------------------------------------------------ instrumentation

class CompileClock:
    """Seconds jax itself reports spending in backend compilation
    (persistent-cache loads included) while active, and how many compile
    requests hit or missed the persistent cache.  Tracing and lowering
    are host Python time and stay on the run side."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, secs, **_):
        if event == self._COMPILE:
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def run_phase(name, fn, *args, **kwargs):
    """Run one phase and print its line.  Nothing is caught: a phase that
    raises ends the process at that phase with its traceback."""
    print(f"[phase] {name}: start", flush=True)
    t0 = time.perf_counter()
    with CompileClock() as clock:
        result = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    print(f"[phase] {name}: ok compile_s={clock.seconds:.1f} "
          f"run_s={max(wall - clock.seconds, 0.0):.1f} "
          f"cache_hits={clock.cache_hits} cache_misses={clock.cache_misses} "
          f"result={json.dumps(result, sort_keys=True)}", flush=True)
    return result


def pallas_census(jaxpr) -> dict:
    """How many ``pallas_call`` equations a program holds and how many of
    them would run INTERPRETED (``interpret=True`` lowers to plain HLO
    loops instead of a Mosaic kernel)."""
    calls = interpreted = 0
    stack = [jaxpr]
    while stack:
        jp = stack.pop()
        jp = getattr(jp, "jaxpr", jp)
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                calls += 1
                interpreted += bool(eqn.params.get("interpret"))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        stack.append(sub)
    return {"pallas_calls": calls, "interpreted": interpreted}


def placement(x) -> dict:
    """Where an array lives: its devices, and one device's shard of its
    shape (equal shapes mean every device holds a whole copy)."""
    return {"devices": sorted(d.id for d in x.sharding.device_set),
            "shape": list(x.shape),
            "shard": list(x.addressable_shards[0].data.shape)}


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ------------------------------------------------------------------ reference

def build_model(cfg):
    """GPTForCausalLM from seed 0 in ``cfg.dtype``, eval mode."""
    import paddle_tpu
    from paddle_tpu.models import GPTForCausalLM
    paddle_tpu.seed(0)
    model = GPTForCausalLM(cfg)
    if cfg.dtype != "float32":
        model.to(dtype=cfg.dtype)
    model.eval()
    return model


def reference_logits(model, seqs) -> list:
    """The model's full causal forward in float32 at ``highest`` matmul
    precision — no cache, no kernels the cache path uses, no batching
    tricks — on each 1-D token sequence of ``seqs``.  Returns one
    ``[len, vocab]`` float32 array per sequence.  Sequences are
    right-padded to one length: causal attention keeps the padding out
    of every real position."""
    from paddle_tpu.nn.functional_call import functional_call, state
    params, buffers = state(model)
    width = -(-max(len(s) for s in seqs) // 16) * 16
    ids = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s

    @jax.jit
    def forward(p, ids):
        p32 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
        with jax.default_matmul_precision("highest"):
            out, _ = functional_call(model, p32, buffers, (ids,),
                                     train=False)
        return out.astype(jnp.float32)

    out = np.asarray(forward(params, jnp.asarray(ids)))
    return [out[i, :len(s)] for i, s in enumerate(seqs)]


def rel_err(got, want) -> float:
    """max|got - want| over max|want|."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        raise AssertionError("non-finite logits")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def argmax_gap(ref, tokens) -> float:
    """How far below the reference's best logit the emitted tokens sit,
    over max|reference|: ``ref [n, vocab]`` are the reference logits at
    the positions that produced ``tokens [n]``.  Sampled tokens flip on
    rounding with random weights; the reference logit of the token the
    system chose cannot be far from the top."""
    ref = np.asarray(ref, np.float32)
    chosen = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(ref.max(-1) - chosen) / np.max(np.abs(ref)))


# ---------------------------------------------------------------------- train

def train_phase(cfg, batch: int, steps: int = 4, lr: float = 3e-4) -> dict:
    """A few steps of a single-chip training program:
    GPTForCausalLM + AdamW(f32 masters when bf16) + functional_call +
    parallel_cross_entropy in ONE donated jit step, on a fixed seeded
    batch.  Loss must be finite and lower at the end."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.meta_parallel.mp_layers import \
        parallel_cross_entropy
    from paddle_tpu.nn.functional_call import functional_call, state

    model = build_model(cfg)        # the step itself runs train=True
    params, buffers = state(model)
    o = opt.AdamW(learning_rate=lr, multi_precision=cfg.dtype != "float32")
    ostate = o.init(params)
    seq = cfg.max_seq_len
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1)))
    x, y = ids[:, :-1], ids[:, 1:]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, os_, x, y):
        def loss_fn(p):
            out, _ = functional_call(model, p, buffers, (x,), train=True)
            return jnp.mean(parallel_cross_entropy(out, y))
        loss, g = jax.value_and_grad(loss_fn)(p)
        newp, nos = o.update(g, os_, p)
        return newp, nos, loss

    t0 = time.perf_counter()
    traced = step.trace(params, ostate, x, y)
    census = pallas_census(traced.jaxpr)
    lowered = traced.lower()
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, ostate, loss = compiled(params, ostate, x, y)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall {losses}")
    return {"layers": cfg.num_layers, "batch": batch, "seq": seq,
            "params": cfg.num_params(),
            "loss": [round(v, 4) for v in losses],
            "trace_lower_compile_s": round(compile_s, 1),
            "step_s": [round(v, 3) for v in step_s],
            "mosaic_calls": mosaic_calls, **census}


# -------------------------------------------------------------------- kernels

# a kernel against its XLA reference computed in float32 at highest
# precision from the SAME bf16 inputs: what is left is the kernel's own
# rounding of its output (2**-9 relative) and of its bf16 intermediates.
# Measured on v5e at the widths below: CHANGES.md, PR 21.
BF16_KERNEL_TOL = 0.02
F32_KERNEL_TOL = 1e-4


def kernels_phase(*, heads: int, head_dim: int, seq: int, slots: int,
                  rows: int, dtype="bfloat16") -> dict:
    """Every Pallas kernel of paddle_tpu/kernels that the serving and
    training phases do not already judge through logits, against its
    reference: flash attention (plain and segment-masked) forward and
    backward, decode attention (one token and a prefill chunk), the
    fused norms forward and backward, fused AdamW.  Returns each
    kernel's ``rel_err`` (max over its outputs and gradients)."""
    from paddle_tpu.kernels.decode_attention import (
        decode_attention, decode_attention_reference)
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    flash_attention_varlen)
    from paddle_tpu.kernels.fused_adamw import fused_adamw_update
    from paddle_tpu.kernels.fused_norm import (fused_layer_norm_pallas,
                                               fused_rms_norm_pallas)
    from paddle_tpu.nn.functional.attention import sdpa_reference
    dt = jnp.dtype(dtype)
    tol = BF16_KERNEL_TOL if dt == jnp.bfloat16 else F32_KERNEL_TOL
    hidden = heads * head_dim
    rs = np.random.RandomState(3)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rs.randn(*shape) * scale, dt)

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    census = {"pallas_calls": 0, "interpreted": 0}

    def count(jitted, *operands):
        for k, v in pallas_census(jitted.trace(*operands).jaxpr).items():
            census[k] += v

    def judge(kernel, reference, args, consts=()):
        """max rel_err of value and gradients (w.r.t. ``args``) of
        ``kernel(*args, *consts)`` against ``reference`` on the same
        inputs in float32.  Everything rides as an operand: an array a
        jitted function closes over is compiled in as a constant."""
        ct = rand(*jax.eval_shape(kernel, *args, *consts).shape)
        wrt = tuple(range(1, len(args) + 1))

        def scalar(fn):
            return lambda ct, *a: jnp.sum(
                fn(*a).astype(jnp.float32) * ct.astype(jnp.float32))
        got_fn = jax.jit(jax.value_and_grad(scalar(kernel), argnums=wrt))
        operands = (ct, *args, *consts)
        count(got_fn, *operands)
        _, got = got_fn(*operands)
        with jax.default_matmul_precision("highest"):
            _, want = jax.jit(jax.value_and_grad(
                scalar(reference), argnums=wrt))(
                    *f32((ct, *args)), *consts)
            out = rel_err(jax.jit(kernel)(*args, *consts),
                          jax.jit(reference)(*f32(args), *consts))
        return max([out] + [rel_err(g, w) for g, w in zip(got, want)])

    result = {}
    qkv = tuple(rand(1, seq, heads, head_dim) for _ in range(3))
    result["flash_attention"] = judge(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        lambda q, k, v: sdpa_reference(q, k, v, is_causal=True), qkv)
    # two packed documents: only same-document pairs attend
    seg = jnp.asarray(np.arange(seq) >= seq // 3, jnp.int32)[None]
    result["flash_attention_varlen"] = judge(
        lambda q, k, v, seg: flash_attention_varlen(q, k, v, seg, seg,
                                                    causal=True),
        lambda q, k, v, seg: sdpa_reference(
            q, k, v, is_causal=True,
            attn_mask=(seg[0][:, None] == seg[0][None, :])[None, None]),
        qkv, consts=(seg,))

    cache = tuple(rand(slots, seq, heads, head_dim) for _ in range(2))
    for name, sq in (("decode_attention", 1),
                     ("decode_attention_chunk", min(64, seq // 2))):
        b = slots if sq == 1 else 1
        q = rand(b, sq, heads, head_dim)
        lens = jnp.asarray(rs.randint(sq, seq + 1, (b,)), jnp.int32)
        kc, vc = (c[:b] for c in cache)
        count(jax.jit(decode_attention), q, kc, vc, lens)
        with jax.default_matmul_precision("highest"):
            want = decode_attention_reference(*f32((q, kc, vc)), lens)
        result[name] = rel_err(
            jax.jit(decode_attention)(q, kc, vc, lens), want)

    x, w, bias = rand(rows, hidden), rand(hidden) + 1, rand(hidden)

    def layer_norm(x, w, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    def rms_norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-5) * w
    result["fused_layer_norm"] = judge(fused_layer_norm_pallas, layer_norm,
                                       (x, w, bias))
    result["fused_rms_norm"] = judge(fused_rms_norm_pallas, rms_norm,
                                     (x, w))

    p, g = rand(hidden, hidden, scale=0.02), rand(hidden, hidden)
    m = jnp.asarray(rs.randn(hidden, hidden) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rs.randn(hidden, hidden)) * 0.01, jnp.float32)
    step, lr, b1, b2, eps, wd = 7, 1e-3, 0.9, 0.999, 1e-8, 0.01
    adamw = jax.jit(functools.partial(
        fused_adamw_update, lr=lr, beta1=b1, beta2=b2, epsilon=eps,
        weight_decay=wd))
    args = (p, g, m, v, jnp.int32(step))
    count(adamw, *args)
    p32, g32 = f32((p, g))
    m2 = b1 * m + (1 - b1) * g32
    v2 = b2 * v + (1 - b2) * g32 * g32
    p2 = p32 - lr * ((m2 / (1 - b1 ** step))
                     / (jnp.sqrt(v2 / (1 - b2 ** step)) + eps) + wd * p32)
    result["fused_adamw"] = max(
        rel_err(a, b_) for a, b_ in zip(adamw(*args), (p2, m2, v2)))

    bad = {k: round(e, 5) for k, e in result.items() if e > tol}
    if bad:
        raise AssertionError(f"kernels: {bad} disagree with their "
                             f"references (tolerance {tol})")
    return {"rel_err": {k: round(e, 5) for k, e in result.items()},
            "tolerance": tol, **census}


# ---------------------------------------------------------------------- serve

def make_traffic(vocab: int, prompt_lens, shared_prefix: int,
                 suffix_lens, seed: int = 7):
    """Wave 1: one seeded prompt per length.  Wave 2: the first
    ``shared_prefix`` tokens of wave 1's longest prompt, then a fresh
    suffix per ``suffix_lens``."""
    rs = np.random.RandomState(seed)
    wave1 = [rs.randint(0, vocab, (n,)).astype(np.int32)
             for n in prompt_lens]
    stem = max(wave1, key=len)[:shared_prefix]
    wave2 = [np.concatenate([stem, rs.randint(0, vocab, (n,))
                             .astype(np.int32)]) for n in suffix_lens]
    return wave1, wave2


def serve_engine(model, waves, new_tokens: int, **engine_kw):
    """Serve ``waves`` (each a list of prompts; a wave is submitted only
    when the one before it has drained) through submit()/step()/result().
    No ``fault_tolerance``: a step that raises, raises here.  Returns
    ``(engine, outputs)`` with one output list per wave."""
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(model, **engine_kw)
    outs = []
    for prompts in waves:
        rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        budget = 200 * (len(prompts) + new_tokens)
        while eng.step():
            budget -= 1
            if budget < 0:
                raise AssertionError("serve: engine did not drain")
        outs.append([eng.result(r) for r in rids])
    return eng, outs


def check_served(eng, outs, model, path: str, tol: float) -> dict:
    """What every serving phase asserts about an engine that has served
    its waves: the decode path, every request finished, the second wave
    hit the prefix cache, the compiled-program counts sit at their
    pins, and every emitted token is a near-argmax of the reference."""
    core = eng.core
    if eng.decode_path != path:
        raise AssertionError(
            f"decode_path {eng.decode_path!r} != {path!r} "
            f"(fallback reason: {eng.decode_fallback_reason!r}, "
            f"tp fusion reason: {eng.tp_fusion_reason!r})")
    flat = [o for wave in outs for o in wave]
    bad = [(o.request_id, o.status, o.status_reason) for o in flat
           if not (o.finished and o.status == "finished")]
    if bad:
        raise AssertionError(f"serve: unfinished requests {bad}")
    hits = sum(o.prefix_hit_tokens for o in outs[-1])
    if len(outs) > 1 and hits <= 0:
        raise AssertionError("serve: second wave missed the prefix cache")
    # the program-set pin: ONE decode, no verify, one prefill per chunk
    # width the plans used, one block gather and one block scatter
    widths = set()
    for o in flat:
        plan = core.scheduler.chunk_plan(o.prefix_hit_tokens,
                                         len(o.prompt), core.prefill_chunk)
        widths.update(w for _, w, _ in plan)
    want = {"decode": 1, "verify": 0, "prefill": len(widths)}
    if dict(core.trace_counts) != want:
        raise AssertionError(
            f"serve: trace counts {core.trace_counts} != pinned {want}")
    pool_counts = dict(core.block_pool.trace_counts)
    if pool_counts != {"gather": 1, "scatter": 1}:
        raise AssertionError(f"serve: block programs {pool_counts}")
    refs = reference_logits(model, [o.sequence[:-1] for o in flat])
    gap = max(argmax_gap(r[len(o.prompt) - 1:], o.tokens)
              for r, o in zip(refs, flat))
    if gap > tol:
        raise AssertionError(
            f"serve: an emitted token sits {gap:.4f} of the logit scale "
            f"below the reference argmax (tolerance {tol})")
    return {"decode_path": eng.decode_path, "requests": len(flat),
            "tokens": sum(len(o.tokens) for o in flat),
            "prefix_hit_tokens": hits,
            "trace_counts": {**core.trace_counts, **pool_counts},
            "prefill_widths": sorted(widths),
            "argmax_gap": round(gap, 5)}


def cache_path_logits(model, ids, chunk: int) -> np.ndarray:
    """Prefill ``ids[:chunk]`` through the KV cache in one chunk, then
    decode the rest one token at a time, each fed the TRUE next token:
    the engine's prefill and decode program bodies
    (``model.decode_step``), returning the ``[len, vocab]`` logits."""
    from paddle_tpu.nn.functional_call import bind_state, state
    params, buffers = state(model)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def step(p, toks, caches, pos):
        with bind_state(model, p, buffers):
            logits, caches = model.decode_step(toks, caches, pos)
        return logits[0].astype(jnp.float32), caches

    ids = jnp.asarray(ids, jnp.int32)
    caches = model.init_cache(1, model.cfg.max_seq_len)
    rows, caches = step(params, ids[None, :chunk], caches, jnp.int32(0))
    out = [np.asarray(rows)]
    for t in range(chunk, len(ids)):
        rows, caches = step(params, ids[None, t:t + 1], caches,
                            jnp.int32(t))
        out.append(np.asarray(rows))
    return np.concatenate(out, 0)


def decode_logits(model, toks, fused: bool):
    """``toks [B, n]`` decoded from EMPTY caches, one column per step,
    through ``fused_decode_step`` or ``decode_step`` at per-row
    positions — the engine's decode program body.  Returns ``([B, n,
    vocab]`` float32 logits, the program's Pallas census)."""
    from paddle_tpu.nn.functional_call import bind_state, state
    params, buffers = state(model)
    b, n = toks.shape
    fn = model.fused_decode_step if fused else model.decode_step

    @functools.partial(jax.jit, donate_argnums=(2,))
    def step(p, col, caches, pos):
        with bind_state(model, p, buffers):
            logits, caches = fn(col[:, None], caches, pos)
        return logits[:, 0].astype(jnp.float32), caches

    caches = [(k, v, jnp.zeros((b,), jnp.int32))
              for k, v, _ in model.init_cache(b, model.cfg.max_seq_len)]
    toks = jnp.asarray(toks, jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    census = pallas_census(step.trace(params, toks[:, 0], caches,
                                      pos).jaxpr)
    out = []
    for t in range(n):
        rows, caches = step(params, toks[:, t], caches, pos + t)
        out.append(np.asarray(rows))
    return np.stack(out, 1), census


def serve_phase(cfg, *, num_slots: int, prompt_lens, shared_prefix: int,
                suffix_lens, new_tokens: int, chunk: int,
                decode_steps: int = 4) -> dict:
    """The default (unfused) engine and the fused engine on ONE model
    instance, each through :func:`serve_engine` / :func:`check_served`,
    plus the logit comparisons the engines' token streams cannot give:
    prefill-then-decode through the cache against the reference, and
    fused against unfused decode.  A fused engine that fell back fails
    :func:`check_served` with the engine's own reason."""
    tol = logit_tol(cfg.dtype)
    model = build_model(cfg)
    waves = make_traffic(cfg.vocab_size, prompt_lens, shared_prefix,
                         suffix_lens)
    kw = dict(num_slots=num_slots, min_bucket=16, prefill_chunk=chunk,
              block_len=16)

    eng, outs = serve_engine(model, waves, new_tokens, **kw)
    result = {"layers": cfg.num_layers,
              "unfused": check_served(eng, outs, model, "unfused", tol)}

    rs = np.random.RandomState(11)
    ids = rs.randint(0, cfg.vocab_size, (chunk + decode_steps,))
    err = rel_err(cache_path_logits(model, ids, chunk),
                  reference_logits(model, [ids])[0])
    if err > tol:
        raise AssertionError(f"serve: prefill-then-decode logits are "
                             f"{err:.4f} from the reference (tol {tol})")
    result["cache_path_rel_err"] = round(err, 5)

    toks = rs.randint(0, cfg.vocab_size, (num_slots, decode_steps))
    want = np.stack(reference_logits(model, list(toks)))
    plain, census = decode_logits(model, toks, fused=False)
    err = rel_err(plain, want)
    if err > tol:
        raise AssertionError(f"serve: unfused decode logits are {err:.4f} "
                             f"from the reference (tol {tol})")
    result["unfused"].update(decode_rel_err=round(err, 5), **census)

    del eng
    feng, fouts = serve_engine(model, waves, new_tokens, fused_decode=True,
                               **kw)
    result["fused"] = check_served(feng, fouts, model, "fused", tol)
    fused, census = decode_logits(model, toks, fused=True)
    err, err_pair = rel_err(fused, want), rel_err(fused, plain)
    if max(err, err_pair) > tol:
        raise AssertionError(
            f"serve: fused decode logits are {err:.4f} from the "
            f"reference, {err_pair:.4f} from unfused (tol {tol})")
    result["fused"].update(decode_rel_err=round(err, 5),
                           vs_unfused_rel_err=round(err_pair, 5), **census)
    return result


# ----------------------------------------------------------------- four chips

def tp_decode_logits(model, mesh, tp: int, toks, pallas_block: bool):
    """:func:`decode_logits` for the tensor-parallel decode program
    (serving/tp.py), slabs kv-head-sharded over ``mesh``.  Returns the
    logits and the placement of one layer's K slab and of the logits."""
    from paddle_tpu.serving import tp as _tp
    from paddle_tpu.serving.kv_pool import KVPool
    b, n = toks.shape
    pool = KVPool.create(model, b, model.cfg.max_seq_len, mesh=mesh)
    program, weights = _tp.build_tp_decode_program(
        model, mesh, tp, pallas_block=pallas_block, batch=b,
        max_seq=pool.max_seq)
    program = jax.jit(program, donate_argnums=(1, 2))
    ks, vs, pos = pool.ks, pool.vs, pool.seq_pos
    toks = jnp.asarray(toks, jnp.int32)
    out = []
    for t in range(n):
        logits, ks, vs, pos = program(weights, ks, vs, pos, toks[:, t])
        out.append(np.asarray(logits[:, 0].astype(jnp.float32)))
    return (np.stack(out, 1),
            {"kv_slab": placement(ks[0]), "logits": placement(logits)})


def serve_tp_phase(cfg, tp: int, *, num_slots: int, prompt_lens,
                   shared_prefix: int, suffix_lens, new_tokens: int,
                   chunk: int, decode_steps: int = 4) -> dict:
    """``tensor_parallel=tp`` serving, composed (``tp_fused``) and with
    ``fused_decode=True`` (``tp_fused_block``), each on a FRESH
    identically-seeded model (the engine shards its model's weights in
    place), judged against a tp=1 twin: token streams against its
    reference forward, decode logits against its reference and against
    each other."""
    tol = logit_tol(cfg.dtype)
    twin = build_model(cfg)
    waves = make_traffic(cfg.vocab_size, prompt_lens, shared_prefix,
                         suffix_lens)
    toks = np.random.RandomState(11).randint(
        0, cfg.vocab_size, (num_slots, decode_steps))
    want = np.stack(reference_logits(twin, list(toks)))
    result = {"layers": cfg.num_layers, "tp": tp}
    seen = {}
    for path, fused in (("tp_fused", False), ("tp_fused_block", True)):
        model = build_model(cfg)
        eng, outs = serve_engine(
            model, waves, new_tokens, num_slots=num_slots, min_bucket=16,
            prefill_chunk=chunk, block_len=16, tensor_parallel=tp,
            fused_decode=fused)
        row = check_served(eng, outs, twin, path, tol)
        logits, where = tp_decode_logits(model, eng.core.mesh, tp, toks,
                                         pallas_block=fused)
        err = rel_err(logits, want)
        if err > tol:
            raise AssertionError(f"{path}: decode logits are {err:.4f} "
                                 f"from the tp=1 reference (tol {tol})")
        where["weights"] = placement(model.gpt.h[0].qkv.weight)
        where["pool_slab"] = placement(eng.core.pool.ks[0])
        for name, at in where.items():
            # sharded for real: tp devices, each holding 1/tp of it
            if len(at["devices"]) != tp \
                    or np.prod(at["shard"]) * tp != np.prod(at["shape"]):
                raise AssertionError(f"{path}: {name} is placed {at}, "
                                     f"not split over {tp} devices")
        row.update(decode_rel_err=round(err, 5), devices=where)
        result[path] = row
        seen[path] = logits
        del eng
    err = rel_err(seen["tp_fused_block"], seen["tp_fused"])
    if err > tol:
        raise AssertionError(f"tp_fused_block vs tp_fused logits {err:.4f}")
    result["block_vs_composed_rel_err"] = round(err, 5)
    return result


def hybrid_train_phase(cfg, degrees: dict, *, batch: int, steps: int = 3,
                       lr: float = 3e-4) -> dict:
    """GPTHybridTrainer over an HybridCommunicateGroup on the visible
    devices for a few steps on a fixed batch: loss finite and falling,
    weights and batch spread over every device of the mesh."""
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import GPTHybridTrainer
    n = int(np.prod(list(degrees.values())))
    dist.topology.set_hybrid_communicate_group(None)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = degrees
    dist.fleet.init(is_collective=True, strategy=strategy,
                    devices=jax.devices()[:n])
    trainer = GPTHybridTrainer(
        cfg, dist.get_hybrid_communicate_group(),
        opt.AdamW(learning_rate=lr, multi_precision=cfg.dtype != "float32"),
        microbatches=max(2 * degrees.get("pp_degree", 1), 1))
    state_ = trainer.init_state()
    x, y = trainer.make_batch(batch=batch)
    where = {"block_weights": placement(
                 jax.tree_util.tree_leaves(state_[1])[0]),
             "batch": placement(x)}
    losses = []
    for _ in range(steps):
        state_, loss = trainer.train_step(state_, x, y)
        losses.append(float(loss))
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"hybrid train {degrees}: loss {losses}")
    at = where["block_weights"]
    if len(at["devices"]) != n or at["shard"] == at["shape"]:
        raise AssertionError(f"hybrid train {degrees}: block weights are "
                             f"placed {at}, not split over {n} devices")
    return {"layers": cfg.num_layers, "degrees": degrees, "batch": batch,
            "loss": [round(v, 4) for v in losses], "devices": where}


# ----------------------------------------------------------------------- main

# depth one 16 GB chip holds at full width: AdamW's f32 masters and
# moments cost 16 bytes a parameter, serving 2 plus the KV slabs and
# the float32 reference's working copy
TRAIN_LAYERS = 2
SERVE_LAYERS = 4


def main() -> None:
    import jaxlib
    dev = jax.devices()[0]
    count = jax.device_count()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={count} jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu} python={sys.version.split()[0]}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found "
                 f"{dev.platform}:{dev.device_kind}; nothing was run")

    # an array a jitted program closes over is compiled in as a constant:
    # at these widths a captured model is gigabytes of host memory per
    # program while it lowers (it killed the first chip runs of this
    # script outright).  Make any such capture an error here.
    jax.config.update("jax_captured_constants_warn_bytes", 64 << 20)
    warnings.filterwarnings(
        "error", message="A large amount of constants were captured")

    from paddle_tpu.device import enable_compile_cache
    from paddle_tpu.models import gpt3_6_7b
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir} entries_before="
          f"{cache_entries(cache_dir)}", flush=True)

    full = gpt3_6_7b()
    print(f"model=gpt3_6_7b h{full.hidden_size} heads{full.num_heads}x"
          f"{full.head_dim} ffn{full.ffn_size} vocab{full.vocab_size} "
          f"s{full.max_seq_len} {full.dtype}; depth cut from "
          f"{full.num_layers} to train={TRAIN_LAYERS} serve={SERVE_LAYERS}",
          flush=True)
    traffic = dict(num_slots=8, prompt_lens=(24, 50, 100, 150),
                   shared_prefix=64, suffix_lens=(40, 72), new_tokens=8,
                   chunk=64)

    train = run_phase("train", train_phase,
                      dataclasses.replace(full, num_layers=TRAIN_LAYERS),
                      batch=2)
    # flash attention took the Pallas route at s2048 and Mosaic compiled
    # every kernel in the step: nothing ran interpreted
    if not (train["pallas_calls"] > 0 and train["interpreted"] == 0
            and train["mosaic_calls"] > 0):
        raise AssertionError(f"train: kernels not compiled by Mosaic "
                             f"{train}")

    kernels = run_phase("kernels", kernels_phase, heads=full.num_heads,
                        head_dim=full.head_dim, seq=full.max_seq_len,
                        slots=traffic["num_slots"], rows=8192)
    if not (kernels["pallas_calls"] > 0 and kernels["interpreted"] == 0):
        raise AssertionError(f"kernels: not compiled by Mosaic {kernels}")

    serve_cfg = dataclasses.replace(full, num_layers=SERVE_LAYERS)
    serve = run_phase("serve", serve_phase, serve_cfg, **traffic)
    for leg in ("unfused", "fused"):
        row = serve[leg]
        if not (row["pallas_calls"] > 0 and row["interpreted"] == 0):
            raise AssertionError(f"serve {leg}: decode kernels not "
                                 f"compiled by Mosaic {row}")

    if count >= 4:
        run_phase("serve_tp4", serve_tp_phase, serve_cfg, 4, **traffic)
        hybrid_cfg = dataclasses.replace(full, num_layers=TRAIN_LAYERS)
        run_phase("hybrid_train_mp2_pp2", hybrid_train_phase, hybrid_cfg,
                  {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2}, batch=4)
        run_phase("hybrid_train_mp4", hybrid_train_phase, hybrid_cfg,
                  {"dp_degree": 1, "mp_degree": 4, "pp_degree": 1}, batch=4)

    print(f"compile cache: {cache_dir} entries_after="
          f"{cache_entries(cache_dir)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
