"""Autoregressive generation as ONE compiled loop.

Reference analog: the decoding loop the reference serves through
``fused_multi_transformer`` + PaddleNLP's ``model.generate`` (greedy /
sampling with temperature, top-k, top-p, eos early-stop).

TPU-native design: the whole token-by-token loop is a single
``lax.scan`` over the functional KV-cache ``decode_step`` — one compiled
program for the entire generation instead of one dispatch per token
(per-dispatch host latency dominates small decode steps).  The prompt is
prefilled in one chunked ``decode_step`` call (causal within the chunk),
then the scan carries ``(caches, last_token, position, rng, finished)``;
shapes are static throughout (``max_new_tokens`` is a trace-time int).

Works on any model exposing ``init_cache(batch, max_len)`` and
``decode_step(input_ids, caches, position)`` (GPTForCausalLM,
LlamaForCausalLM).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["generate", "beam_search"]


def _filter_top_k(logits, k: int):
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _filter_top_p(logits, p: float):
    """Nucleus filtering: keep the smallest prefix of the probability-
    sorted vocab whose mass reaches ``p`` (the top token always stays)."""
    sorted_idx = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, sorted_idx, -1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    # token i is kept while the mass BEFORE it is < p
    before = jnp.cumsum(probs, axis=-1) - probs
    keep_sorted = before < p
    inv = jnp.argsort(sorted_idx, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, -1)
    return jnp.where(keep, logits, -jnp.inf)


def generate(model, input_ids, max_new_tokens: int, do_sample: bool = False,
             temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
             eos_token_id: Optional[int] = None,
             pad_token_id: Optional[int] = None, seed: int = 0,
             output_scores: bool = False, prompt_lens=None):
    """Generate ``max_new_tokens`` continuations of ``input_ids``
    ([batch, prompt_len], dense — no padding) and return the full
    sequences [batch, prompt_len + max_new_tokens].

    ``do_sample=False`` is greedy; sampling applies ``temperature`` then
    ``top_k`` (0 = off) then ``top_p`` (1.0 = off).  With
    ``eos_token_id`` set, rows that emit it keep emitting
    ``pad_token_id`` (default: the eos id) for the remaining steps.
    ``output_scores=True`` additionally returns the pre-sampling float32
    logits of every generated position [batch, max_new_tokens, vocab].

    ``prompt_lens`` ([batch] int32, optional) admits RAGGED right-padded
    prompts: row r's real prompt is ``input_ids[r, :prompt_lens[r]]``.
    Prefill masks the pad tail (the ragged decode-attention seq_lens mask
    — pad keys are never attended by live queries) and each row's decode
    starts at its OWN length, overwriting the pad region of the cache
    token by token.  The generated tokens still land in the trailing
    ``max_new_tokens`` columns of the result; row r's true sequence is
    ``concat(input_ids[r, :prompt_lens[r]], result[r, prompt_len:])``.
    """
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if do_sample and temperature <= 0:
        raise ValueError("temperature must be > 0 when sampling")
    b, s0 = input_ids.shape
    max_seq = getattr(getattr(model, "cfg", None), "max_seq_len", None)
    if max_seq is not None and s0 + max_new_tokens > max_seq:
        raise ValueError(
            f"prompt_len {s0} + max_new_tokens {max_new_tokens} exceeds "
            f"the model's max_seq_len {max_seq} (position table size) — "
            "out-of-range positions would silently clamp")
    input_ids = jnp.asarray(input_ids)
    pad = eos_token_id if pad_token_id is None else pad_token_id
    if prompt_lens is not None:
        lens = jnp.asarray(prompt_lens, jnp.int32)
        if lens.shape != (b,):
            raise ValueError(f"prompt_lens must be [{b}], got {lens.shape}")
        import numpy as _np
        if not isinstance(lens, jax.core.Tracer):
            host = _np.asarray(lens)
            if host.min() < 1 or host.max() > s0:
                raise ValueError("prompt_lens entries must lie in "
                                 f"[1, {s0}]")

    def pick(key, logits):
        logits = logits.astype(jnp.float32)
        if not do_sample:
            return jnp.argmax(logits, axis=-1).astype(input_ids.dtype)
        logits = logits / temperature
        if top_k:
            logits = _filter_top_k(logits, top_k)
        if top_p < 1.0:
            logits = _filter_top_p(logits, top_p)
        return jax.random.categorical(key, logits,
                                      axis=-1).astype(input_ids.dtype)

    caches = model.init_cache(b, s0 + max_new_tokens)
    logits, caches = model.decode_step(input_ids, caches, 0)
    if prompt_lens is None:
        last_logits = logits[:, -1]
    else:
        # each row's last VALID prompt position carries its next-token
        # distribution; pad-tail logits are garbage and are skipped
        last_logits = jnp.take_along_axis(
            logits, (lens - 1)[:, None, None], axis=1)[:, 0]
    first_scores = last_logits.astype(jnp.float32)
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    first = pick(sub, last_logits)
    if eos_token_id is not None:
        finished = first == eos_token_id
    else:
        finished = jnp.zeros((b,), bool)

    def body(carry, _):
        caches, tok, pos, key, finished = carry
        # ``pos`` is the sequence index of ``tok``, the token being fed
        # (a [b] vector when prompts are ragged — each row decodes at its
        # own offset; models/kv_cache.py handles the per-row cache write)
        logits, caches = model.decode_step(tok[:, None], caches, pos)
        key, sub = jax.random.split(key)
        scores = logits[:, 0].astype(jnp.float32)
        nxt = pick(sub, logits[:, 0])
        if eos_token_id is not None:
            nxt = jnp.where(finished, jnp.asarray(pad, nxt.dtype), nxt)
            finished = finished | (nxt == eos_token_id)
        return (caches, nxt, pos + 1, key, finished), (nxt, scores)

    if prompt_lens is not None:
        # prefill ran at scalar offset 0, so each layer's cache tuple
        # carries the scalar position s0; re-anchor it to the per-row
        # lengths so decode WRITES land at each row's own offset and the
        # attention lens mask the pad tail (models/kv_cache.py semantics)
        caches = [(c[0], c[1], lens) for c in caches]
    if max_new_tokens > 1:
        # ``first`` sits at sequence index s0 (row r: prompt_lens[r]) —
        # that is the position the first scan step feeds it at
        pos0 = jnp.asarray(s0, jnp.int32) if prompt_lens is None else lens
        carry = (caches, first, pos0, key, finished)
        _, (rest, rest_scores) = jax.lax.scan(body, carry, None,
                                              length=max_new_tokens - 1)
        new_tokens = jnp.concatenate(
            [first[:, None], jnp.moveaxis(rest, 0, 1)], axis=1)
        scores = jnp.concatenate(
            [first_scores[:, None], jnp.moveaxis(rest_scores, 0, 1)], axis=1)
    else:
        new_tokens = first[:, None]
        scores = first_scores[:, None]
    seq = jnp.concatenate([input_ids, new_tokens], axis=1)
    return (seq, scores) if output_scores else seq


def _repeat_beams(tree, k: int, batch: int):
    """Tile every batch-leading leaf of a cache pytree k times
    ([b, ...] -> [b*k, ...]); scalars (e.g. position counters) pass
    through."""
    def leaf(a):
        if getattr(a, "ndim", 0) >= 1 and a.shape[0] == batch:
            return jnp.repeat(a, k, axis=0)
        return a
    return jax.tree_util.tree_map(leaf, tree)


def _gather_beams(tree, flat_idx, bk: int):
    """Reorder batch-leading leaves by ancestor beam indices."""
    def leaf(a):
        if getattr(a, "ndim", 0) >= 1 and a.shape[0] == bk:
            return jnp.take(a, flat_idx, axis=0)
        return a
    return jax.tree_util.tree_map(leaf, tree)


def beam_search(model, input_ids, max_new_tokens: int, beam_size: int = 4,
                length_penalty: float = 0.0,
                eos_token_id: Optional[int] = None,
                pad_token_id: Optional[int] = None):
    """Beam-search decoding as ONE compiled loop (the expansion step and
    ancestor reordering live inside a single ``lax.scan``; KV caches are
    tiled to ``batch*beam`` rows and gathered per step by beam index).

    Reference analog: the beam decode the reference ships through
    ``nn.BeamSearchDecoder`` / PaddleNLP ``model.generate(
    decode_strategy='beam_search')``.  Finished beams (emitted
    ``eos_token_id``) are frozen: they continue with ``pad_token_id``
    (default: eos) at no score change.  Final ranking uses
    ``score / (n_generated ** length_penalty)`` (0 = raw log-prob).

    Returns ``(sequences [batch, prompt+max_new], scores [batch])`` for
    the best beam of each batch row.

    Cache contract: beam tiling/reordering identifies batch-leading cache
    leaves by ``shape[0] == batch`` — every cache leaf must either lead
    with the batch dimension or have a leading dim different from the
    batch size (a non-batch leaf whose leading dim coincidentally equals
    the batch would be mis-tiled; the shipped GPT/Llama caches satisfy
    the contract by construction).
    """
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if beam_size < 1:
        raise ValueError("beam_size must be >= 1")
    b, s0 = input_ids.shape
    k = beam_size
    max_seq = getattr(getattr(model, "cfg", None), "max_seq_len", None)
    if max_seq is not None and s0 + max_new_tokens > max_seq:
        raise ValueError(
            f"prompt_len {s0} + max_new_tokens {max_new_tokens} exceeds "
            f"the model's max_seq_len {max_seq}")
    input_ids = jnp.asarray(input_ids)
    pad = eos_token_id if pad_token_id is None else pad_token_id
    if pad is None:
        pad = 0  # buffer fill only; without eos every slot is written

    # prefill once at batch b, then tile caches to b*k beam rows
    caches = model.init_cache(b, s0 + max_new_tokens)
    logits, caches = model.decode_step(input_ids, caches, 0)
    logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
    vocab = logp.shape[-1]
    # the prefill can seed at most `vocab` distinct beams; wider widths
    # (e.g. an exhaustive beam in tests) fill the rest with -inf scores
    # that real candidates displace in later expansion steps
    k0 = min(k, vocab)
    scores, first = jax.lax.top_k(logp, k0)          # [b, k0] each
    if k0 < k:
        scores = jnp.concatenate(
            [scores, jnp.full((b, k - k0), -jnp.inf, scores.dtype)], 1)
        first = jnp.concatenate(
            [first, jnp.repeat(first[:, :1], k - k0, axis=1)], 1)
    caches = _repeat_beams(caches, k, b)
    bk = b * k

    tokens0 = jnp.full((b, k, max_new_tokens), pad, input_ids.dtype)
    tokens0 = tokens0.at[:, :, 0].set(first.astype(input_ids.dtype))
    if eos_token_id is not None:
        finished0 = first == eos_token_id
    else:
        finished0 = jnp.zeros((b, k), bool)

    def body(carry, t):
        caches, tokens, last, scores, finished = carry
        # ``last`` (buffer slot t-1) sits at sequence index s0 + t - 1 —
        # that is the position it must be fed at (same convention the
        # review pinned for generate())
        logits, caches = model.decode_step(
            last.reshape(bk, 1), caches, s0 + t - 1)
        logp = jax.nn.log_softmax(
            logits[:, 0].astype(jnp.float32), -1).reshape(b, k, vocab)
        if eos_token_id is not None:
            # frozen beams: exactly one zero-cost continuation slot, all
            # else -inf.  The slot's INDEX is clamped into vocab (pad may
            # legitimately sit past the base vocab — appended pad ids);
            # the actually-emitted token is rewritten to ``pad`` below,
            # so the clamp never leaks into the output.
            slot = min(pad, vocab - 1)
            frozen = jnp.full((vocab,), -jnp.inf).at[slot].set(0.0)
            logp = jnp.where(finished[..., None], frozen, logp)
        cand = scores[..., None] + logp               # [b, k, V]
        scores, idx = jax.lax.top_k(cand.reshape(b, k * vocab), k)
        beam_idx = idx // vocab                       # ancestor beam
        tok = (idx % vocab).astype(tokens.dtype)      # new token
        flat = (jnp.arange(b)[:, None] * k + beam_idx).reshape(-1)
        caches = _gather_beams(caches, flat, bk)
        tokens = jnp.take_along_axis(tokens, beam_idx[..., None], axis=1)
        if eos_token_id is not None:
            prev_finished = jnp.take_along_axis(finished, beam_idx, axis=1)
            tok = jnp.where(prev_finished, jnp.asarray(pad, tok.dtype), tok)
            finished = prev_finished | (tok == eos_token_id)
        tokens = tokens.at[:, :, t].set(tok)
        return (caches, tokens, tok, scores, finished), None

    carry = (caches, tokens0, first.astype(input_ids.dtype), scores,
             finished0)
    if max_new_tokens > 1:
        carry, _ = jax.lax.scan(body, carry,
                                jnp.arange(1, max_new_tokens))
    _, tokens, _, scores, _ = carry

    if length_penalty != 0.0:
        if eos_token_id is not None:
            # generated length up to and including the first eos
            pos = jnp.argmax(tokens == eos_token_id, axis=-1)
            has = jnp.any(tokens == eos_token_id, axis=-1)
            n_gen = jnp.where(has, pos + 1, max_new_tokens)
        else:
            n_gen = jnp.full((b, k), max_new_tokens)
        final = scores / (n_gen.astype(jnp.float32) ** length_penalty)
    else:
        final = scores
    best = jnp.argmax(final, axis=1)                  # [b]
    best_tokens = jnp.take_along_axis(
        tokens, best[:, None, None], axis=1)[:, 0]    # [b, max_new]
    best_scores = jnp.take_along_axis(final, best[:, None], axis=1)[:, 0]
    seq = jnp.concatenate(
        [input_ids, best_tokens.astype(input_ids.dtype)], axis=1)
    return seq, best_scores
