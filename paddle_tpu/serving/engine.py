"""Continuous-batching engine core: the fixed-shape step loop.

Device plane (all jitted, all fixed-shape — graftlint's recompile-hazard
rule is the design constraint):

  * ``prefill``  — one program per CHUNK WIDTH: ``[1, width]`` tokens
    appended into a ``[1, max_seq]`` staging cache at a traced offset,
    returning the last-valid-token logits (a traced valid count selects
    the row, so padding never recompiles).  Width comes from the
    scheduler's chunk plan: without chunking, one pow2-bucketed chunk
    covers the whole uncached suffix (the classic shape); with
    ``prefill_chunk`` set, long suffixes run as fixed-width pieces
    interleaved with decode, so one 8k admission never stalls the
    in-flight streams for more than one chunk;
  * ``block copy`` — the radix prefix cache's two programs
    (kv_pool.BlockPool): gather matched prefix blocks into the staging
    cache at admission, scatter freshly computed blocks out of the slot
    at prefill completion.  A cache-hit request prefills ONLY its
    suffix — prefill FLOPs drop by the shared-prefix fraction and TTFT
    becomes O(suffix);
  * ``decode``   — ONE program, period: ``[num_slots, 1]`` tokens against
    the whole pool with per-slot positions (models/kv_cache.py), per-slot
    sampling params as traced row values, and per-slot PRNG keys.  Free
    and mid-prefill slots ride along as no-ops: their rows decode garbage
    that nothing reads, their writes land at positions a later adopt
    overwrites wholesale;
    A model with a RECURRENT state (``model.recurrent_state_spec``: a
    state-space layer's, of fixed size per request) has it carried
    beside the KV slabs as an opaque pytree: ``[num_slots, ...]`` arrays
    in the pool, donated through this same program; ``[1, ...]`` arrays
    in a prefill's staging, threaded chunk to chunk, with the chunk's
    ``valid`` count handed to the model (a recurrence does not forgive
    padding as a length mask does) and written over the slot's row at
    ``adopt``.  A free slot's state row rides along like its KV row;
  * ``verify``   — ONE program (speculative decoding, ``spec_k > 0``):
    ``[num_slots, spec_k+1]`` draft windows — each slot's last committed
    token followed by its host-proposed n-gram draft (serving/spec.py) —
    at per-slot positions, with matched-sampling acceptance computed
    in-program: the window replays the EXACT per-token split/sample
    chain sequential decode would run, a slot commits its longest
    draft prefix that matches those samples plus one bonus token, and
    ``seq_pos`` advances only by the accepted length, so rejected rows'
    KV writes sit past every visible position and the next append
    overwrites them.  Accepted lengths vary per slot; shapes never do.

Host plane: ONE device->host readback per step phase — the decode
harvest reads a sampled token vector once, and a step that completes
prefills reads their batched first tokens once (all prefill dispatches
stay async until then).  Admission, radix-tree matching, eviction,
eos/length bookkeeping and metrics all run on host ints the engine
already holds.

One program ahead: nothing in the decode program needs the host between
steps (the last tokens, positions, keys and slabs are device arrays one
step hands to the next), so a step dispatches ITS decode program first
and only then reads the tokens of the program the PREVIOUS step
dispatched (``_InFlight``): while the host waits for, harvests and
accounts one program's tokens the chip already runs the next.  Where the
next dispatch needs this step's tokens on the host (speculation: the
drafts come from them) the same code reads each program in the step
that dispatched it (``EngineCore.overlap``).

Per-slot sampling reuses ``generation._filter_top_p`` directly (its
threshold broadcasts over rows) and generalises ``_filter_top_k`` to a
per-row traced k via rank masking (``_filter_top_k_rows`` — the static-k
form cannot vary k within one compiled step).  Each stage of that tail
sits under a ``lax.cond`` on the rows' own parameters (``sample_rows``):
an all-greedy batch runs the argmax alone, the rank mask runs only where
a sampling row has ``top_k > 0`` and the nucleus filter only where one
has ``top_p < 1`` — bit-equal to running every stage, in the SAME one
decode program.  Each slot draws from its OWN PRNG key with the same
split discipline as ``generate`` (the split stays outside the conds), so
a single-request engine run reproduces ``generate(seed=...)`` token for
token, sampling included.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import math
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models.generation import _filter_top_p
from ..nn.functional_call import bind_state, state
from ..obs.parts import program_name, program_parts
from .aot import (AOTStoreError, RECURRENT_STATE_REFUSAL,
                  aot_fingerprint, engine_aot_context)
from .errors import EngineStalledError, RequestRejected
from .health import (DegradationLadder, EngineHealth,
                     FaultToleranceConfig)
from .kv_pool import BlockPool, KVPool, recurrent_state_spec, zero_state
from .metrics import ServingMetrics
from .prefix_cache import MatchResult, PrefixCache
from .scheduler import Request, Scheduler

__all__ = ["EngineCore", "sample_rows", "finite_or_sentinel",
           "NONFINITE_SENTINEL"]

# graftprog (tools/analysis/compile_surface.py) entry-point marker: the
# engine core is a registered compile-surface root — every jit program
# it can build must appear on the static manifest.  Pure data, read by
# the AST analysis only; zero runtime effect.
__compile_surface_roots__ = ("EngineCore",)

# graftmem (tools/analysis/memory.py) byte declarations: the engine
# plane's persistent device state OUTSIDE the derived pool slabs, as
# closed-form byte formulas over capacity fields.  ``row_state`` legs
# are the per-slot decode vectors ``_build_device_plane`` allocates
# (last token i32, PRNG key pair u32x2, sampling params bool+f32+i32+f32,
# logit mask bool[vocab]); ``staging`` is the single-slot prefill cache
# (per-slab k+v at the model dtype; ``num_layers`` and ``kv_heads`` are
# the POOL's: its slabs and the heads one slab holds, whose product is
# planes x the model's kv heads for every family, kv_pool.
# cache_geometry; ``v_slabs`` its V slabs, ``num_layers`` again or 0
# where a position holds one row kind, kv_pool.cache_row).  Pure data, read by the AST
# analysis and pinned against runtime measurement by
# tests/test_zz_memory_surface.py; zero runtime effect.
__memory_bytes__ = {
    "row_state._last_tok": "4 * num_slots",
    "row_state._keys": "8 * num_slots",
    "row_state._sampling_dev": "13 * num_slots",
    "row_state._mask_dev": "num_slots * vocab_size",
    "row_state.recurrent_state": "num_slots * state_bytes_per_slot",
    "staging": "(num_layers + v_slabs) * max_seq * kv_heads * head_dim"
               " * itemsize + state_bytes_per_slot",
}

# token-readback encoding of the device-side health check: a decode row
# whose logits hold a non-finite value reads back as this instead of a
# token id (ids are always >= 0, so the sentinel is unambiguous) — the
# watchdog detects poisoned steps without adding a second device sync
NONFINITE_SENTINEL = -1


def finite_or_sentinel(logits, toks):
    """Encode per-row logits health into the sampled-token vector:
    ``toks[r]`` when ``logits[r]`` is all-finite, else
    :data:`NONFINITE_SENTINEL`.  Runs inside the decode program (and on
    the prefill first-token path), so non-finite detection rides the
    step's existing single readback."""
    ok = jnp.all(jnp.isfinite(logits), axis=-1)
    return jnp.where(ok, toks, NONFINITE_SENTINEL)


def _filter_top_k_rows(logits, top_k):
    """Per-row top-k: keep each row's ``top_k[r]`` highest logits
    (``top_k[r] == 0`` keeps the whole row).  Rank masking — argsort of
    the descending argsort — matches ``generation._filter_top_k`` for
    distinct values and resolves ties by vocab order (the stable-sort
    winner), which is also what argmax picks for k=1."""
    order = jnp.argsort(-logits, axis=-1)
    rank = jnp.argsort(order, axis=-1)
    k = jnp.asarray(top_k, jnp.int32)[:, None]
    keep = jnp.where(k > 0, rank < k, True)
    return jnp.where(keep, logits, -jnp.inf)


def sample_rows(keys, logits, do_sample, temperature, top_k, top_p,
                mask=None):
    """Per-row token selection over ``logits [rows, vocab]``.

    ``do_sample [rows] bool`` picks greedy argmax vs sampling per row;
    sampling rows apply ``temperature -> top_k -> top_p`` (the exact
    pipeline of ``generation.generate``) and draw from their OWN key row
    of ``keys [rows, key_dim]``, so one request's randomness never
    depends on its slot neighbours.

    Each stage sits under a ``lax.cond`` on what the rows ask for,
    computed here from the operands themselves: no row samples -> the
    argmax alone (no scale, sort, gather or draw); the rank mask only
    where a sampling row has ``top_k > 0``, the nucleus filter only
    where one has ``top_p < 1``.  A row that does not ask for a stage
    gets its logits back unchanged from it anyway, so a skipped stage
    changes no value: the result is bit-equal to the ungated pipeline
    for every row, inside ONE program of the same signature.

    ``mask [rows, vocab] bool`` (constrained decoding) bans False
    columns BEFORE everything — greedy argmax and the filter pipeline
    both see ``-inf`` there, so a constrained row renormalizes over its
    allowed set exactly like rejection-free constrained sampling.  The
    mask is a traced operand of the existing decode/verify programs:
    unconstrained rows pass all-True and the program set never grows."""
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    greedy_tok = jnp.argmax(logits, axis=-1)
    do_sample = jnp.asarray(do_sample, bool)
    k = jnp.asarray(top_k, jnp.int32)
    p = jnp.asarray(top_p, jnp.float32)

    def nucleus(x):
        # rows with top_p == 1.0 skip the nucleus filter EXACTLY,
        # matching generate()'s static skip; filtered rows take the
        # nucleus lane
        col = p[:, None]
        return jnp.where(col >= 1.0, x, _filter_top_p(x, col))

    def sample():
        temp = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
        x = logits / temp[:, None]
        x = jax.lax.cond(jnp.any(do_sample & (k > 0)),
                         lambda x: _filter_top_k_rows(x, k),
                         lambda x: x, x)
        x = jax.lax.cond(jnp.any(do_sample & (p < 1.0)),
                         nucleus, lambda x: x, x)
        sampled = jax.vmap(jax.random.categorical)(keys, x)
        return jnp.where(do_sample, sampled, greedy_tok)

    return jax.lax.cond(jnp.any(do_sample), sample, lambda: greedy_tok)


@jax.jit
def _first_token(key, logits, do_sample, temperature, top_k, top_p, mask):
    """A completed prefill's first token (sentinel-encoded, shape
    ``[1]``): the decode tail on the one row ``logits [vocab]``, as ONE
    small program.  Jitted because an eager ``lax.cond`` traces its
    branches anew, and so compiles anew, on every call."""
    first = sample_rows(key[None], logits[None], do_sample, temperature,
                        top_k, top_p, mask=mask[None])
    return finite_or_sentinel(logits[None], first)


@jax.jit
def _first_token_counted(touched, *sampling):
    """:func:`_first_token` with the request's per-chunk expert counts
    behind it, ``[1 + chunks]``: they ride the first token's readback."""
    return jnp.concatenate([_first_token(*sampling), touched])


def _advance_live(seq_pos, new_pos):
    """The positions a decode or verify program hands back: a slot
    parked at 0 (free, or claimed and still prefilling: ``KVPool.free``
    parks it, ``adopt`` sets a live length of at least 1) STAYS at 0.
    Its ride-along token rewrites row 0 and attends to itself alone.
    Left to advance, a free slot's phantom length grew a row a step and
    the in-place attention kernel, which streams what ``seq_lens``
    says is live, read all of it (PERF.md, PR 29)."""
    return jnp.where(seq_pos > 0, new_pos, 0)


def _verify_tail(logits, drafts, draft_len, keys, do_sample, temperature,
                 top_k, top_p, mask, spec_k):
    """Matched-sampling acceptance over one verify window (runs inside
    the jitted verify program, after the model produced ``logits
    [rows, spec_k+1, vocab]``).

    The Python loop unrolls the EXACT per-token chain sequential decode
    runs — one ``jax.random.split`` per emitted token per slot, sample
    from the split's second half, carry the first — so position t's
    sample is identical to what the t-th sequential decode step would
    have drawn.  A slot's accepted length is its longest draft prefix
    matching those samples (``cumprod`` of the running match), and the
    committed tokens ARE the samples: greedy AND seeded runs are
    token-for-token identical to non-speculative decode by
    construction, and for temperature sampling the emitted tokens are
    literally draws from the sequential target distribution
    (rejection-sampling-correct with an exact-match acceptance rule).

    Each position is sentinel-encoded through ``finite_or_sentinel``
    first; the sentinel (-1) never equals a draft id (>= 0), so a
    poisoned position terminates acceptance by itself — at most ONE
    sentinel (the bonus slot) ever reaches the host, where the harvest
    fails the request exactly as sequential decode would have.

    Returns ``(committed [rows, spec_k+1] int32, accepted [rows] int32,
    new_keys [rows, ...])`` with ``new_keys`` the key-chain entry after
    ``accepted+1`` splits — the key sequential decode would hold."""
    carry = keys
    samples = []
    carries = [carry]
    for t in range(spec_k + 1):
        split = jax.vmap(lambda kk: jax.random.split(kk, 2))(carry)
        tok = sample_rows(split[:, 1], logits[:, t], do_sample,
                          temperature, top_k, top_p, mask=mask)
        tok = finite_or_sentinel(logits[:, t], tok)
        samples.append(tok.astype(jnp.int32))
        carry = split[:, 0]
        carries.append(carry)
    committed = jnp.stack(samples, axis=1)        # [rows, K+1]
    key_chain = jnp.stack(carries, axis=1)        # [rows, K+2, ...]
    if spec_k:
        valid = jnp.arange(spec_k)[None, :] < draft_len[:, None]
        match = (committed[:, :spec_k] == drafts) & valid
        accepted = jnp.sum(
            jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    else:
        accepted = jnp.zeros(committed.shape[:1], jnp.int32)
    new_keys = jax.vmap(lambda kc, a: kc[a])(key_chain, accepted + 1)
    return committed, accepted, new_keys


class _PartsRecorded:
    """A jitted program (``functools.partial(jitted, weights)``) that
    records which part of the model each of its operations belongs to,
    once per compile: ahead of the first call at a new shape it lowers
    and compiles THAT program over the call's own operands, reads the
    optimized text into a table (``obs.parts.program_parts``) and hands
    it to the telemetry, which keeps it as a ``program.parts`` span on
    the engine lane.  jit's own call then reuses the trace this lowering
    made (jax caches it: the engine's trace counters move once) and
    compiles again, the first time in a checkout.  ``func`` and ``args``
    are the partial's, for what lowers or exports the program itself."""

    def __init__(self, program, metrics: ServingMetrics):
        self.func, self.args = program.func, program.args
        self._program, self._metrics = program, metrics
        self._shapes = set()

    def __call__(self, *args):
        # the caches (operands 0 and 1) keep their shape for the
        # engine's life; what is behind them is what a compile is keyed
        # by: a chunk's ``[1, width]`` ids
        shape = args[2].shape
        if shape not in self._shapes:
            self._shapes.add(shape)
            # the compile cache's key leaves metadata out: a hit may
            # hand back an executable another tree compiled, whose text
            # carries that tree's ``op_name``s.  Not for this compile.
            flag = "jax_compilation_cache_include_metadata_in_key"
            keyed = getattr(jax.config, flag)
            jax.config.update(flag, True)
            try:
                text = self.func.lower(*self.args,
                                       *args).compile().as_text()
            finally:
                jax.config.update(flag, keyed)
            self._metrics.on_program_parts(program_name(text),
                                           program_parts(text))
        return self._program(*args)


class _Slot:
    """Host mirror of one pool slot's request progress."""

    __slots__ = ("req", "pos", "match", "draft", "allowed", "pending",
                 "parked")

    def __init__(self, req: Request, prompt_len: int,
                 match: Optional[MatchResult] = None,
                 draft=None, allowed=None):
        self.req = req
        # cache length as far as the host has HARVESTED: the device's
        # row count is ``pos + pending``
        self.pos = prompt_len
        self.match = match          # pinned radix-cache path, if any
        self.draft = draft          # per-request NGramDraftTable (spec)
        self.allowed = allowed      # frozenset of allowed token ids
        # programs dispatched for this request whose token the host has
        # not read yet (0 or 1)
        self.pending = 0
        # the row was parked on the device ahead of the slot's release
        self.parked = False


class _InFlight:
    """One dispatched decode (or verify) program whose tokens the host
    has not read.  It carries what the harvest needs WITHOUT looking at
    the engine's present: ``owners``, the ``{slot: _Slot}`` the program
    computed a token for, taken at dispatch (a slot released and adopted
    again in between has another ``_Slot``, so the older program's token
    can never reach the newer request), and ``step``, the spans of the
    step that dispatched it: the counts that arrive with the tokens (a
    routing model's experts touched) land on THAT step's span beside the
    program's other counts, whichever step reads them.  It sits in
    ``EngineCore._inflight`` from its dispatch until its tokens are on
    the host (or its plane is rebuilt), and leaves it once."""

    __slots__ = ("toks", "owners", "step", "drafted")

    def __init__(self, toks, owners: Dict[int, _Slot], step,
                 drafted: Optional[int]):
        self.toks = toks
        self.owners = owners
        self.step = step            # metrics.StepSpans of the dispatch
        self.drafted = drafted      # draft tokens of a verify window


class _Prefill:
    """A request mid-prefill: its slot is allocated, its context grows in
    a per-request staging cache (per-layer [1, max_seq] k/v rows seeded
    from the radix cache's matched blocks), and the scheduler's chunk
    plan drives one decode_step append per chunk."""

    __slots__ = ("req", "slot", "ks", "vs", "state", "plan", "next_chunk",
                 "match", "last_logits", "touched", "chunk_spans")

    def __init__(self, req: Request, slot: int, ks, vs, plan,
                 match: Optional[MatchResult], state=(), touched=None):
        self.req = req
        self.slot = slot
        self.ks = ks                # staging caches, threaded per chunk
        self.vs = vs
        self.state = state          # the model's recurrent state, ditto
        self.plan = plan            # [(offset, width, valid), ...]
        self.next_chunk = 0
        self.match = match
        self.last_logits = None     # final chunk's last-token logits
        # a routing model's experts touched per chunk, on the device
        # (``[chunks a row can take]`` int32) until the first token's
        # readback, and the chunk spans that wait for them
        self.touched = touched
        self.chunk_spans = []

    @property
    def done(self) -> bool:
        return self.next_chunk >= len(self.plan)


class EngineCore:
    """Owns the pool, the radix prefix cache, the per-slot device state
    and the compiled step functions.  The public request/streaming
    surface lives in ``serving.api.ServingEngine``."""

    def __init__(self, model, num_slots: int = 8,
                 max_seq: Optional[int] = None,
                 min_bucket: int = 16,
                 max_prefills_per_step: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 max_prefill_tokens_per_step: Optional[int] = None,
                 enable_prefix_cache: bool = True,
                 block_len: int = 16,
                 prefix_blocks: Optional[int] = None,
                 metrics: Optional[ServingMetrics] = None,
                 fused_decode: bool = False,
                 fault_tolerance: Optional[FaultToleranceConfig] = None,
                 faults=None,
                 max_queue: Optional[int] = None,
                 tensor_parallel: int = 1,
                 collective_fusion: bool = True,
                 journal=None,
                 aot_store=None,
                 spec_k: int = 0):
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if prefill_chunk is not None and prefill_chunk < min_bucket:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be >= min_bucket "
                f"{min_bucket}")
        if max_prefill_tokens_per_step is not None \
                and max_prefill_tokens_per_step < 1:
            raise ValueError("max_prefill_tokens_per_step must be >= 1")
        if enable_prefix_cache and block_len < 1:
            raise ValueError("block_len must be >= 1")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None)")
        # what this model says the engine may not do with it, each with
        # its reason (a model with a recurrent state: models/jamba.py)
        self.model_refusals: Dict[str, str] = dict(
            getattr(model, "serving_refusals", dict)())
        if enable_prefix_cache and "prefix_cache" in self.model_refusals:
            raise ValueError(
                "enable_prefix_cache=True (and the fleet handoff built "
                "on it): " + self.model_refusals["prefix_cache"])
        if aot_store is not None and recurrent_state_spec(model):
            raise AOTStoreError(RECURRENT_STATE_REFUSAL)
        if aot_store is not None and "aot_store" in self.model_refusals:
            raise AOTStoreError(self.model_refusals["aot_store"])
        self.model = model
        self.num_slots = num_slots
        self.prefill_chunk = prefill_chunk
        self.max_prefill_tokens_per_step = max_prefill_tokens_per_step
        self.metrics = metrics or ServingMetrics()
        # ---- robustness plumbing (docs/serving.md "Fault tolerance"):
        # the watchdog (step retry/backoff, degradation ladder,
        # quarantine rebuild, circuit breaker) engages only with an
        # explicit fault_tolerance config — without one the engine
        # raises exactly as before, so callers that own recovery keep
        # their semantics.  Deadlines, cancel() and backpressure are
        # always available.
        self.faults = faults                    # serving/faults.py hook
        # durable request journal (serving/journal.py, docs/serving.md
        # "Crash recovery"): submit records are written by the API
        # facade, terminal records by _finalize, and the per-step
        # delivered high-water marks batch at the END of the step —
        # every site guards `if journal is None` (the faults pattern),
        # so a journal-less engine pays nothing and compiles nothing new
        self.journal = journal
        self._journal_hwm: Dict[int, int] = {}
        # zero-cold-start (docs/serving.md "Zero cold start"): with an
        # attached AOT store the engine LOADS its compiled-program set
        # instead of tracing it — every site guards `if aot_store is
        # None` / on the loaded-handle dicts, so a store-less engine
        # pays nothing and compiles exactly as before.  _warm_buckets
        # (the committed chunk-width set) is derived after the
        # scheduler exists; _attach_aot runs after the decode path
        # resolves, and again from every _build_device_plane rebuild.
        self.aot_store = aot_store
        self.aot_status: Optional[str] = None
        self._warm_buckets: Optional[frozenset] = None
        self._aot_prefill: Dict[int, Callable] = {}
        self.fault_tolerant = fault_tolerance is not None
        self.ft = fault_tolerance if fault_tolerance is not None \
            else FaultToleranceConfig()
        self.health = EngineHealth(self.ft)
        self.ladder = DegradationLadder(self.ft.ladder_threshold)
        self.prefix_bypass = False              # ladder: cache disabled
        # ---- speculative decoding (docs/serving.md "Speculative
        # decoding"): spec_k > 0 arms the draft/verify path — per-slot
        # n-gram drafts (serving/spec.py) verified by ONE batched
        # [num_slots, spec_k+1] program.  Static legality resolves with
        # the decode path (_resolve_decode_path -> spec_on /
        # spec_fallback_reason); spec_bypass is the ladder's runtime
        # kill switch (a spec_verify fault ladder disables speculation
        # and the engine keeps serving one token per step).
        self.spec_k = spec_k
        self.spec_on = False
        self.spec_fallback_reason: Optional[str] = None
        self.spec_bypass = False                # ladder: spec disabled
        self.max_queue = max_queue if max_queue is not None \
            else self.ft.max_queue
        # monotone work marker: tokens emitted, admissions, prefill
        # chunks and terminal dispositions all bump it — the
        # run_until_complete stall detector watches it flatline
        self.progress_counter = 0
        self._deadlines_possible = False        # skip the per-step scan
        self._fault_phase: Optional[str] = None  # watchdog attribution
        # device-plane construction args, kept verbatim so a quarantine
        # rebuild (_build_device_plane) re-runs the same construction
        self._max_seq_arg = max_seq
        self._enable_prefix_cache = enable_prefix_cache
        self._block_len_arg = block_len
        self._prefix_blocks_arg = prefix_blocks
        # compiled-program trace counters: ONE decode fn + ONE prefill
        # fn whose jit cache is keyed by the [1, width] chunk shape (one
        # program per chunk width / pow2 bucket, nothing per length);
        # these (plus BlockPool.trace_counts for the two block-copy
        # programs) are what the compile-count guard tests assert on.
        # Engine-lifetime: a quarantine rebuild re-traces ON TOP of them
        # (exactly one more decode program, the same bucket set).
        self.trace_counts = {"prefill": 0, "decode": 0, "verify": 0}
        self._compile_seen: Dict[str, int] = {}
        # telemetry plumbing: the step index keys every phase span; the
        # step currently executing tags lazily-built programs' obs
        # events so they correlate with the surrounding serving.step span
        self._step_index = 0
        self._step_in_flight = 0
        # ---- tensor-parallel serving (docs/serving.md "Tensor-parallel
        # serving"): tp > 1 shards the WHOLE device plane over a 1-D
        # mesh — model weights Megatron-style, KV slot/block slabs on
        # the kv-head axis — and every compiled program becomes a
        # per-mesh SPMD program with its set size unchanged.  The decode
        # step additionally takes the fused compute-collective shard_map
        # path (serving/tp.py) when collective_fusion is on and the
        # model supports it; otherwise the composed GSPMD decode serves.
        if tensor_parallel < 1:
            raise ValueError(
                f"tensor_parallel must be >= 1, got {tensor_parallel}")
        self.tensor_parallel = tensor_parallel
        self.collective_fusion = collective_fusion
        self.mesh = None
        self._tp_program = None
        self._tp_program_path: Optional[str] = None
        self._tp_verify_program = None
        self._tp_verify_program_path: Optional[str] = None
        self.tp_fusion_reason: Optional[str] = None
        if tensor_parallel > 1:
            from . import tp as _tp
            # every construction-failure check runs BEFORE
            # shard_model_params mutates the caller's model in place: a
            # caller catching the ValueError and retrying at tp=1 must
            # get back an untouched single-device model, not one whose
            # weights were already laid out over a mesh
            cfg = model.cfg
            refusal = _tp.serving_refusal(model)
            if refusal is not None:
                raise ValueError(
                    f"tensor_parallel {tensor_parallel}: {refusal}")
            kv_heads = getattr(cfg, "kv_heads", None) or cfg.num_heads
            if kv_heads % tensor_parallel:
                raise ValueError(
                    f"kv_heads {kv_heads} must divide evenly over "
                    f"tensor_parallel {tensor_parallel} (the KV slot "
                    f"slabs partition on the kv-head axis)")
            self.mesh = _tp.build_serving_mesh(tensor_parallel)
            # GSPMD layout for the whole program set: prefill chunks,
            # staging init, gather/scatter, adopt and the sampling tail
            # all compile against the sharded weights
            _tp.shard_model_params(model, self.mesh)
        self.metrics.set_tp_degree(tensor_parallel)
        # times a token passes the model's layer stack in one forward
        # step (a looped model's cfg says; 1 otherwise): the
        # ``loop_passes`` of the step and prefill spans
        self.loop_passes = int(getattr(model.cfg, "loop_passes", 1))
        self._build_device_plane()
        self.scheduler = Scheduler(num_slots, self.pool.max_seq,
                                   min_bucket=min_bucket,
                                   max_prefills_per_step=max_prefills_per_step)
        # fused decode-block path (kernels/decode_block.py): opt-in flag,
        # resolved STATICALLY here — legality (shape/dtype/VMEM plan) and
        # routing never depend on runtime values, so the decode program
        # set stays {chunk} + buckets + ONE decode either way.  The
        # resolution lands in the decode_block obs event at compile time.
        self.fused_decode = fused_decode
        self.decode_path, self.decode_fallback_reason = \
            self._resolve_decode_path()
        # the committed bucket set is pinned ONCE, at construction —
        # the AOT builder enumerates the same set from an identically
        # configured engine, and _run_chunk's drift guard holds every
        # later plan width against it (ladder degradations only ever
        # shrink the reachable set, never escape it)
        self._warm_buckets = frozenset(self.warm_buckets())
        if self.aot_store is not None:
            self._attach_aot()

    def warm_buckets(self) -> Tuple[int, ...]:
        """The COMMITTED prefill chunk-width set: every width
        ``Scheduler.chunk_plan`` can emit for THIS configuration, over
        every reachable plan start (0, any block-aligned radix-cache
        match, and each chunk-stride position past those) — for both
        the chunked ladder rung and the chunking-disabled one, since
        the degradation ladder can drop ``prefill_chunk`` mid-life.
        This is the contract surface between the AOT builder and the
        runtime: the builder exports exactly one prefill program per
        width here, and ``_run_chunk`` raises (never silently traces)
        on a width outside it while a store is attached."""
        max_seq = self.pool.max_seq
        mb = max(self.scheduler.min_bucket, 1)
        chunk = self.prefill_chunk
        starts = {0}
        if self.block_pool is not None:
            starts.update(range(0, max_seq, self.block_pool.block_len))
        positions = set(starts)
        if chunk is not None:
            for s in starts:
                positions.update(range(s, max_seq, chunk))
        widths = set()
        for pos in positions:
            cap = max_seq - pos
            if cap < 1:
                continue
            # bucket_length values are {mb * 2^k} capped at the row
            # remainder — enumerate the ladder once per start
            b = mb
            while True:
                widths.add(min(b, cap))
                if chunk is not None:
                    widths.add(min(b, cap, chunk))
                if b >= cap:
                    break
                b *= 2
        if chunk is not None:
            widths.add(chunk)
        return tuple(sorted(widths))

    def _attach_aot(self) -> None:
        """Warm-load the compiled-program set from the attached store:
        one prefill per committed bucket width, gather + scatter into
        the block pool, the ONE decode at the resolved path.  Any miss
        (fingerprint skew, absent leg) or failed load (corrupt
        artifact, injected fault) degrades THAT program to
        trace-on-demand with an ``aot_miss``/``aot_fallback`` event —
        never a crash.  A bucket-set disagreement under a MATCHING
        fingerprint is different: builder and runtime no longer agree
        on the committed widths, the contract itself broke, and the
        engine refuses loudly."""
        store = self.aot_store
        t0 = time.perf_counter()
        self.aot_status = None
        self._aot_prefill = {}
        fp = aot_fingerprint(engine_aot_context(self))
        if fp != store.fingerprint:
            self.aot_status = "skew"
            self.metrics.on_aot_miss(
                "store", f"fingerprint skew: engine {fp[:12]}, store "
                         f"{store.fingerprint[:12]}")
            return
        committed = tuple(sorted(self._warm_buckets)) \
            if self._warm_buckets is not None \
            else self.warm_buckets()
        if tuple(store.widths) != tuple(committed):
            raise AOTStoreError(
                f"committed bucket drift under a matching fingerprint: "
                f"store built for widths {list(store.widths)}, runtime "
                f"enumerates {list(committed)} — builder and engine "
                f"disagree on warm_buckets()")
        wanted = 0
        loads = 0
        for w in committed:
            wanted += 1
            fn = self._aot_load(f"prefill:w{w}", donate=(0, 1))
            if fn is not None:
                self._aot_prefill[w] = fn
                loads += 1
        if self._aot_prefill:
            self._prefill_fn = self._make_aot_prefill_dispatch()
        if self.block_pool is not None:
            wanted += 2
            fn = self._aot_load("gather")
            if fn is not None:
                self.block_pool._load_fn = fn
                loads += 1
            fn = self._aot_load("scatter", donate=(0, 1))
            if fn is not None:
                self.block_pool._store_fn = fn
                loads += 1
        wanted += 1
        fn = self._aot_load(f"decode:{self.decode_path}",
                            donate=(0, 1))
        if fn is not None:
            # observability parity with the traced build: the
            # decode_block event still records which path this
            # engine's single decode program runs
            self._emit_decode_block()
            self._decode_fn = fn
            loads += 1
        if self.spec_on:
            wanted += 1
            fn = self._aot_load(f"verify:{self.decode_path}",
                                donate=(0, 1))
            if fn is not None:
                self._verify_fn = fn
                loads += 1
        self.aot_status = "warm" if loads == wanted else \
            ("partial" if loads else "empty")
        if loads:
            self.metrics.on_aot_load(loads, time.perf_counter() - t0,
                                     build_s=store.build_seconds)

    def _aot_load(self, name: str,
                  donate: Tuple[int, ...] = ()) -> Optional[Callable]:
        """Load ONE program from the store, or None with the
        degradation event recorded (the caller then leaves the traced
        lazy-build path in place)."""
        store = self.aot_store
        if store is None:
            return None
        if not store.has(name):
            self.metrics.on_aot_miss(name, "not in store")
            return None
        try:
            if self.faults is not None:
                self.faults.fire("aot_load")
            return store.load_call(name, donate=donate, mesh=self.mesh)
        except Exception as e:
            self.metrics.on_aot_fallback(name, repr(e))
            return None

    def _make_aot_prefill_dispatch(self) -> Callable:
        """A ``_prefill_fn``-shaped dispatcher over the warm-loaded
        per-width programs.  A committed width whose artifact failed to
        load falls back to ONE lazily traced prefill (jit re-keys it
        per width exactly as the cold path would)."""
        loaded = self._aot_prefill
        traced: Dict[str, Optional[Callable]] = {"fn": None}

        def prefill_dispatch(ks, vs, ids, pos, valid):
            fn = loaded.get(int(ids.shape[1]))
            if fn is None:
                if traced["fn"] is None:
                    self.metrics.on_aot_fallback(
                        f"prefill:w{int(ids.shape[1])}",
                        "width artifact unavailable; tracing")
                    traced["fn"] = self._build_prefill_fn()
                fn = traced["fn"]
            return fn(ks, vs, ids, pos, valid)

        return prefill_dispatch

    def _build_device_plane(self) -> None:
        """Construct (or, on quarantine, RECONSTRUCT) everything that
        lives on the device or mirrors it: the KV pools, the prefix
        cache, per-slot row state and the compiled-program handles.  The
        scheduler, metrics, health state and queue are deliberately NOT
        touched — a rebuild must preserve queued work and telemetry.
        Fresh handles mean the jit wrappers re-trace on next use; the
        program SET stays {chunk} + buckets + ONE decode (pinned by the
        chaos suite's post-quarantine compile test)."""
        model, num_slots = self.model, self.num_slots
        self.pool = KVPool.create(model, num_slots, self._max_seq_arg,
                                  mesh=self.mesh)
        self.pool.faults = self.faults
        self.metrics.set_kv_planes(self.pool.planes)
        self.metrics.set_state_bytes(self.pool.state_bytes_per_slot)
        self.metrics.set_cache_row_bytes(self.pool.row_bytes)
        # whether the programs carry a recurrent state beside the slabs
        self._stateful = bool(self.pool.state_bytes_per_slot)
        # a model with expert layers (``expert_routing_spec``: (expert
        # layers, experts)): its programs pass the live mask in, count
        # the rows each expert got, and carry the running load
        # ``[expert layers, experts]`` on the device
        routing = getattr(model, "expert_routing_spec", None)
        self._routed = routing is not None
        if self._routed and self._stateful:
            raise ValueError("a model with both a recurrent state and "
                             "expert layers has no program here")
        self._expert_load = None
        if self._routed:
            self._expert_load = jnp.zeros(routing(), jnp.int32)
            self.metrics.set_moe_experts(math.prod(routing()))
        self.prefix_cache: Optional[PrefixCache] = None
        self.block_pool: Optional[BlockPool] = None
        # once the degradation ladder bypassed the cache, a quarantine
        # rebuild must not re-allocate its block slab: _cache_active
        # guarantees nothing would ever read or write it again
        if self._enable_prefix_cache and not self.prefix_bypass:
            block_len = self._block_len_arg
            # block_len must tile the slot row; shrink to the largest
            # pow2 divisor of max_seq when the requested size doesn't
            # (pow2 max_seqs — the common case — keep a pow2 request
            # verbatim).  Round DOWN to a pow2 first: halving a non-pow2
            # like 12 would otherwise walk 12->6->3->1 past the perfectly
            # good 8 and quietly build a per-token tree.
            block_len = 1 << (block_len.bit_length() - 1)
            while block_len > 1 and self.pool.max_seq % block_len:
                block_len //= 2
            # default pool size: as many blocks as the slot pool has rows
            # of context — a second slab the size of the first
            nb = self._prefix_blocks_arg \
                if self._prefix_blocks_arg is not None else \
                num_slots * (self.pool.max_seq // block_len)
            self.block_pool = BlockPool.create(model, nb, block_len,
                                               self.pool.max_seq,
                                               mesh=self.mesh)
            self.block_pool.faults = self.faults
            self.prefix_cache = PrefixCache(self.block_pool)
            self.prefix_cache.faults = self.faults
            # evictions land on THIS engine's timeline lane, not the
            # tracer's default lane 0 (another engine's, under sharing)
            self.prefix_cache.on_event = functools.partial(
                self.metrics.tracer.event, lane=self.metrics.engine_lane)
        self._slots: Dict[int, _Slot] = {}
        self._prefills: List[_Prefill] = []      # FCFS, mid-prefill
        # dispatched programs the host has not read, oldest first: one
        # between steps where the engine runs a program ahead, none
        # otherwise (a second only while a faulted step is retried)
        self._inflight: collections.deque = collections.deque()
        # per-slot device row state (fixed [num_slots] shapes)
        self._last_tok = jnp.zeros((num_slots,), jnp.int32)
        key0 = jax.random.PRNGKey(0)
        self._keys = jnp.tile(key0[None], (num_slots,) + (1,) * key0.ndim)
        if self.mesh is not None:
            # born on the mesh: an array's type carries its mesh, so
            # single-device row state would make the decode program's
            # second call (fed its own mesh-resident outputs) a new
            # signature — a second trace and compile of the ONE decode
            from . import tp as _tp
            self._last_tok = _tp.replicated(self._last_tok, self.mesh)
            self._keys = _tp.replicated(self._keys, self.mesh)
        # per-slot sampling params: host numpy mirrors, re-uploaded to a
        # cached device copy only when admission/eviction dirties them
        # (values are traced row data — changing them never recompiles)
        self._do_sample = np.zeros((num_slots,), bool)
        self._temperature = np.ones((num_slots,), np.float32)
        self._top_k = np.zeros((num_slots,), np.int32)
        self._top_p = np.ones((num_slots,), np.float32)
        self._sampling_dev: Optional[Tuple] = None
        # per-slot allowed-token mask (constrained decoding): host rows
        # dirtied on admission/release, lazily re-uploaded like the
        # sampling params — all-True rows are unconstrained, and the
        # mask is traced row data in the SAME decode/verify programs
        self._mask_host = np.ones(
            (num_slots, int(model.cfg.vocab_size)), bool)
        self._mask_dev = None
        self._decode_fn = None
        self._verify_fn = None
        self._prefill_fn: Optional[Callable] = None
        self._staging_init_fn: Optional[Callable] = None
        # a rebuilt BlockPool's trace counters restart at zero: drop the
        # stale baseline so its re-traces still emit compile events
        self._compile_seen = {k: v for k, v in self._compile_seen.items()
                              if not k.startswith("block_")}
        # quarantine: the rebuilt plane re-loads from artifacts instead
        # of re-tracing (the first construction-time call runs from
        # __init__ once the decode path is resolved; _warm_buckets is
        # still None here on that first pass)
        if self.aot_store is not None and self._warm_buckets is not None:
            self._attach_aot()

    def _lane(self, req: Request) -> int:
        """Tracer lane for one request's lifecycle spans (the engine's
        own step-phase timeline sits on ``metrics.engine_lane``; lanes
        are per-engine blocks, so engines sharing a tracer never
        collide)."""
        return self.metrics.request_lane(req.request_id)

    # ----------------------------------------------------------- prefill
    def _model_weights(self):
        """``(params, buffers)`` of the model, the weight operand of
        every composed program.  The programs take the parameters as
        OPERAND 0, bound with ``functools.partial`` so dispatch sites
        keep the ``(ks, vs, ...)`` signature: an array a jitted function
        closes over is compiled into the program as a constant — one
        private copy of the whole model per program, in host memory
        while it lowers and in HBM once loaded.  Harmless at test
        sizes; at published widths it is gigabytes per prefill width."""
        return state(self.model)

    def _with_parts(self, program):
        """``program`` as a TRACED engine holds it (its tracer has an
        ``annotate``: a device trace may be running): behind
        :class:`_PartsRecorded`.  An untraced engine holds the program
        itself."""
        if self.metrics.tracer.annotate is None:
            return program
        return _PartsRecorded(program, self.metrics)

    def _build_prefill_fn(self) -> Callable:
        model, stateful = self.model, self._stateful
        routed, chunk = self._routed, self._chunk_stride
        params, buffers = self._model_weights()

        def prefill(params, ks, vs, ids, pos, valid, state=None,
                    load=None, touched=None):
            self.trace_counts["prefill"] += 1  # trace-time side effect
            caches = [(k, v, pos) for k, v in zip(ks, vs)]
            with bind_state(model, params, buffers):
                if routed:
                    # padding past ``valid`` reaches no expert
                    logits, caches, rows = model.decode_step(
                        ids, caches, pos, valid=valid)
                elif not stateful:
                    logits, caches = model.decode_step(ids, caches, pos)
                else:
                    # padding past ``valid`` must leave a recurrent
                    # state as token ``valid - 1`` left it: the count
                    # goes INTO the model
                    logits, caches, state = model.decode_step(
                        ids, caches, pos, state=state, valid=valid)
            # what the engine adds behind the model is the program's
            # ``sampling`` part (obs/parts.py)
            with jax.named_scope("sampling"):
                last = jnp.take_along_axis(
                    logits, (valid - 1)[None, None, None], axis=1)[0, 0]
                out = (last.astype(jnp.float32),
                       [c[0] for c in caches], [c[1] for c in caches])
                if routed:
                    # the running load, and this chunk's experts touched
                    # at the chunk's own place in the request's vector
                    return out + (
                        load + rows, touched.at[pos // chunk].set(
                            jnp.count_nonzero(rows).astype(jnp.int32)))
            return out + (state,) if stateful else out

        # donating the staging rows (and state) threads them chunk to
        # chunk in place; a model without a recurrent state is called
        # without the operand and lowers to the program it always did
        donate = (1, 2, 6) if stateful else \
            (1, 2, 7, 8) if routed else (1, 2)
        return functools.partial(
            jax.jit(prefill, donate_argnums=donate), params)

    @property
    def _chunk_stride(self) -> int:
        """Positions between the starts of a request's prefill chunks
        (the whole row without chunking)."""
        return self.prefill_chunk or self.pool.max_seq

    def _prefill_cost(self, req: Request) -> int:
        """Tokens of prefill work admitting ``req`` costs THIS step: the
        width of its first chunk, after the radix-cache match shrinks the
        suffix.  This is what the scheduler's head-of-line budget check
        sees — a long-prompt head with a long cached prefix is cheap."""
        matched = self.prefix_cache.match_length(req.prompt) \
            if self._cache_active else 0
        plan = self.scheduler.chunk_plan(matched, req.prompt_len,
                                         self.prefill_chunk)
        return plan[0][1]

    @property
    def _cache_active(self) -> bool:
        """Prefix cache exists AND the degradation ladder has not
        bypassed it."""
        return self.prefix_cache is not None and not self.prefix_bypass

    def prefix_probe(self, prompt) -> int:
        """Longest radix-cached prefix of ``prompt`` in TOKENS, without
        admitting, pinning, or touching the device — a pure host walk of
        the radix tree (``PrefixCache.match_length``).  This is the
        replica-affinity signal the fleet router routes on: the replica
        whose cache already holds the longest prefix serves the request
        with the least recompute.  0 when the cache is off, bypassed by
        the degradation ladder, or simply cold."""
        if not self._cache_active:
            return 0
        return self.prefix_cache.match_length(prompt)

    # ------------------------------------------------ fleet KV handoff
    # The disaggregated fleet (docs/serving.md "Disaggregated fleet")
    # moves a finished prompt's radix blocks between replicas through
    # these two halves.  Both ride the EXISTING compiled surface: the
    # export is the prefix cache's one gather program, the adopt is the
    # slot-adopt copy + the one scatter program — the handoff adds zero
    # new compiled programs (pinned by the disagg chaos suite).

    def export_prompt_kv(self, prompt) -> Optional[MatchResult]:
        """PREFILL-side half: pin ``prompt``'s cached block path so the
        transfer window cannot lose it to LRU eviction.  Returns the
        pinned :class:`MatchResult` (``tokens == 0`` when nothing is
        cached), or None when the cache is off/bypassed.  The caller
        (serving/handoff.py) MUST hand the result back to
        :meth:`release_export` on every path — commit or abort."""
        if not self._cache_active:
            return None
        return self.prefix_cache.match(prompt, count_stats=False)

    def _mesh_scope(self):
        """The mesh context the engine's handoff copies dispatch under
        (a no-op scope on single-chip engines) — the same push
        ``_step_impl`` performs for the step programs."""
        if self.mesh is not None:
            return jax.set_mesh(self.mesh)
        return contextlib.nullcontext()

    def export_gather(self, match: MatchResult):
        """Read the pinned blocks into per-layer ``[1, max_seq, h, d]``
        staging rows via THE gather program (``BlockPool.load_row``)."""
        with self._mesh_scope():
            return self.prefix_cache.load_staging(match)

    def release_export(self, match: Optional[MatchResult]) -> None:
        """Unpin an export (idempotent — ``PrefixCache.release``).  A
        quarantine rebuild may have dropped the cache entirely
        (``prefix_cache = None`` under ladder bypass); the pinned nodes
        then belong to a discarded tree and nothing reads their
        refcounts again, so the release is a safe no-op — it must not
        crash the handoff's abort path."""
        if match is not None and self.prefix_cache is not None:
            self.prefix_cache.release(match)

    def adopt_prompt_kv(self, prompt, ks, vs, tokens: int,
                        faults=None) -> int:
        """DECODE-side half: land ``tokens`` transferred prompt tokens
        (staging rows ``ks``/``vs`` from the source's
        :meth:`export_gather`) in THIS engine's radix cache.  The rows
        stage through a transient pool slot — the scatter program's only
        legal source — which is freed again on every path, so the
        transfer can never leak a slot.  Returns the number of new
        blocks written (0: cache off/bypassed, or everything already
        cached here).  Raises when no slot is free — the caller gates on
        ``pool.free_slots`` and defers.  ``faults`` is the ROUTER-level
        injector: ``handoff_scatter`` fires after the slot claim, so
        the chaos suite proves the try/finally unwinding for real."""
        if not self._cache_active or tokens < self.block_pool.block_len:
            return 0
        slot = self.pool.alloc()
        try:
            if faults is not None:
                faults.fire("handoff_scatter")
            with self._mesh_scope():
                self.pool.adopt(slot, list(zip(ks, vs)), tokens,
                                set_pos=False)
                return self.prefix_cache.insert(
                    np.asarray(prompt)[:tokens], self.pool, slot)
        finally:
            self.pool.free(slot)

    def _contained_cache_fault(self, match: Optional[MatchResult],
                               exc: Exception) -> None:
        """A prefix-cache operation raised under the watchdog: unpin
        whatever was matched, count the fault toward the ladder (which
        bypasses the cache entirely at threshold) and let the admission
        continue as a plain cache miss — the cache is an optimization,
        never a correctness dependency."""
        if match is not None:
            self.prefix_cache.release(match)
        self._subsystem_fault("prefix_cache", exc)

    def _begin_prefill(self, req: Request) -> None:
        """Claim a slot, match + pin the longest cached prefix, seed the
        staging cache from its block rows (one gather program), and queue
        the suffix's chunk plan.  No model FLOPs run here.  The slot and
        the pinned radix path are returned to their pools if anything
        between claim and placement raises — admission failure must not
        bleed capacity (resource-lifecycle rule)."""
        t_admit = time.perf_counter()
        slot = self.pool.alloc()
        match = None
        try:
            matched = 0
            t_match0 = t_match1 = t_admit
            if self._cache_active:
                t_match0 = time.perf_counter()
                try:
                    match = self.prefix_cache.match(req.prompt)
                    matched = match.tokens
                except Exception as e:
                    if not self.fault_tolerant:
                        raise
                    self._contained_cache_fault(match, e)
                    match, matched = None, 0
                t_match1 = time.perf_counter()
            t_gather0 = time.perf_counter()
            state = ()      # a recurrent state, where the model has one
            touched = None  # experts touched per chunk, where it routes
            if matched:
                try:
                    ks, vs = self.prefix_cache.load_staging(match)
                except Exception as e:
                    if not self.fault_tolerant:
                        raise
                    # degrade THIS admission to a miss (fresh staging,
                    # full-prompt prefill) and keep serving
                    self._contained_cache_fault(match, e)
                    match, matched = None, 0
            if not matched:
                # ONE compiled zero-staging builder instead of 2*num_layers
                # eager jnp.zeros dispatches per miss admission
                if self._staging_init_fn is None:
                    self._staging_init_fn = self._build_staging_init_fn()
                staged = self._staging_init_fn()
                ks, vs = staged[:2]
                if self._stateful:
                    state = staged[2]
                elif self._routed:
                    touched = staged[2]
            t_gather1 = time.perf_counter()
            plan = self.scheduler.chunk_plan(matched, req.prompt_len,
                                             self.prefill_chunk)
            self.scheduler.place(req, slot)
            # hit/telemetry accounting only after placement: a failed
            # admission is requeued and retried, and must not count its
            # hit (or record its lifecycle spans) twice
            if matched:
                req.prefix_hit_tokens = matched
                self.metrics.on_prefix_hit(matched)
            req.admit_time = t_admit
            self.metrics.on_queue_wait(t_admit - req.arrival_time)
            self.metrics.on_gather(t_gather1 - t_gather0)
            tracer = self.metrics.tracer
            if tracer.enabled:
                lane = self._lane(req)
                tracer.set_lane_name(lane, f"request {req.request_id}")
                rid = req.request_id
                tracer.add_span("queued", lane, req.arrival_time, t_admit,
                                prompt_len=req.prompt_len, request=rid,
                                step=self._step_in_flight)
                if self._cache_active:
                    tracer.add_span("prefix_match", lane, t_match0,
                                    t_match1, hit_tokens=matched,
                                    request=rid)
                tracer.add_span("gather", lane, t_gather0, t_gather1,
                                hit=bool(matched), request=rid)
            self._prefills.append(_Prefill(req, slot, ks, vs, plan, match,
                                           state=state, touched=touched))
            self.progress_counter += 1          # admission = progress
        except BaseException:
            if match is not None:
                self.prefix_cache.release(match)
            self.pool.free(slot)
            raise

    def _build_staging_init_fn(self) -> Callable:
        """The compiled builder of one request's zeroed staging rows
        (per-layer ``[1, max_seq, kv_heads, head_dim]`` K and V lists).
        Under a mesh the rows are born kv-head-sharded, the layout (and
        so the type) every later chunk's staging output has — one
        prefill trace per width."""
        model, max_seq = self.model, self.pool.max_seq

        state_spec = self.pool.state_spec if self._stateful else None
        chunks = -(-max_seq // self._chunk_stride) if self._routed else 0

        def fresh_staging():
            caches = model.init_cache(1, max_seq)
            rows = [c[0] for c in caches], [c[1] for c in caches]
            if chunks:
                return rows + (jnp.zeros((chunks,), jnp.int32),)
            if state_spec is None:
                return rows
            return rows + (zero_state(state_spec, 1),)

        sharding = None
        if self.mesh is not None:
            from . import tp as _tp
            sharding = jax.sharding.NamedSharding(self.mesh,
                                                  _tp.KV_SLAB_SPEC)
        return jax.jit(fresh_staging, out_shardings=sharding)

    def _run_chunk(self, st: _Prefill) -> None:
        """Dispatch one prefill chunk of ``st`` (async — no readback)."""
        if self._prefill_fn is None:
            self._prefill_fn = self._build_prefill_fn()
            self._prefill_fn = self._with_parts(self._prefill_fn)
        off, width, valid = st.plan[st.next_chunk]
        if self.aot_store is not None and self._warm_buckets is not None \
                and width not in self._warm_buckets:
            # the committed-bucket contract (warm_buckets) broke: with
            # a store attached this must be LOUD, not a silent trace
            raise AOTStoreError(
                f"prefill width {width} is outside the committed "
                f"bucket set {sorted(self._warm_buckets)} — "
                f"warm_buckets()/chunk_plan drift")
        t0 = time.perf_counter()
        ids = np.zeros((1, width), np.int32)
        ids[0, :valid] = np.asarray(st.req.prompt[off:off + valid],
                                    np.int32)
        if self._stateful:
            last_logits, st.ks, st.vs, st.state = self._prefill_fn(
                st.ks, st.vs, jnp.asarray(ids),
                jnp.asarray(off, jnp.int32), jnp.asarray(valid, jnp.int32),
                st.state)
        elif self._routed:
            last_logits, st.ks, st.vs, self._expert_load, st.touched = \
                self._prefill_fn(
                    st.ks, st.vs, jnp.asarray(ids),
                    jnp.asarray(off, jnp.int32),
                    jnp.asarray(valid, jnp.int32), None,
                    self._expert_load, st.touched)
        else:
            last_logits, st.ks, st.vs = self._prefill_fn(
                st.ks, st.vs, jnp.asarray(ids),
                jnp.asarray(off, jnp.int32), jnp.asarray(valid, jnp.int32))
        t1 = time.perf_counter()
        st.next_chunk += 1
        st.req.prefill_chunks += 1
        self.progress_counter += 1              # chunk ran = progress
        self.metrics.on_prefill_chunk(valid)
        self.metrics.step_count("prefill_tokens", valid)
        rows_of = getattr(self.model, "attended_rows", None)
        # staging rows a layer of the chunk's program read, where the
        # model says
        attended = {} if rows_of is None else {"attended_rows": rows_of(
            self._staging_shape(), off, width, self.pool.ks[0].dtype)}
        span = self.metrics.tracer.add_span(
            "prefill_chunk", self._lane(st.req), t0, t1,
            chunk=st.next_chunk - 1, width=width, tokens=valid,
            offset=off, request=st.req.request_id,
            # whether the chunk started from an earlier chunk's
            # recurrent state (False on a request's first, and always
            # for a model that carries none)
            state_carried=self._stateful and st.next_chunk > 1,
            **attended)
        if self._routed and span is not None:
            # ``experts_touched`` lands with the first token's readback
            st.chunk_spans.append((off // self._chunk_stride, span))
        if st.done:
            st.last_logits = last_logits

    def _complete_prefill(self, st: _Prefill):
        """Final chunk done: sample the first token with the request's
        own key and adopt the staging row into the pool slot.  Returns
        ``(st, first_token_array)`` — the caller batches the readbacks
        (``_flush_staged``), and only THEN publishes the prompt blocks
        to the radix cache: the first token doubles as the device-side
        finiteness probe, and KV whose prefill produced non-finite
        logits must never be inserted where future admissions would
        copy it."""
        req, slot = st.req, st.slot
        key = jax.random.PRNGKey(req.sampling.seed)
        key, sub = jax.random.split(key)
        s = req.sampling
        allowed = None
        self._mask_host[slot] = True
        if req.allowed_tokens is not None:
            # constrained decoding: the per-slot vocab mask constrains
            # the FIRST token here and every later one inside the
            # decode/verify programs; the host set gates draft proposals
            allowed = frozenset(int(t) for t in req.allowed_tokens)
            self._mask_host[slot] = False
            self._mask_host[slot, np.asarray(req.allowed_tokens,
                                             np.int64)] = True
        self._mask_dev = None
        # host arrays go in as the call's operands: no eager transfer
        # programs of their own ahead of it
        sampling = (sub, st.last_logits,
                    np.asarray([s.do_sample], bool),
                    np.asarray([s.temperature], np.float32),
                    np.asarray([s.top_k], np.int32),
                    np.asarray([s.top_p], np.float32),
                    self._mask_host[slot])
        first = _first_token_counted(st.touched, *sampling) \
            if self._routed else _first_token(*sampling)
        draft = None
        if self.spec_on:
            from .spec import NGramDraftTable
            draft = NGramDraftTable()
            draft.seed(req.prompt)
        self.pool.adopt(slot, list(zip(st.ks, st.vs)), req.prompt_len,
                        state=st.state if self._stateful else None)
        self._slots[slot] = _Slot(req, req.prompt_len, match=st.match,
                                  draft=draft, allowed=allowed)
        self._last_tok = self._last_tok.at[slot].set(first[0])
        self._keys = self._keys.at[slot].set(key)
        self._do_sample[slot] = s.do_sample
        self._temperature[slot] = s.temperature
        self._top_k[slot] = s.top_k
        self._top_p[slot] = s.top_p
        self._sampling_dev = None
        self.metrics.on_prefill(req.prompt_len - req.prefix_hit_tokens)
        return st, first

    def _advance_one(self, st: _Prefill, staged: List) -> None:
        """Advance one mid-prefill request — to completion without
        chunking, by exactly one chunk with it — appending the completed
        ``(st, first_token)`` to ``staged``.  Under the watchdog, a
        prefill-execution fault is PRECISELY attributable (unlike a
        decode fault, which spans every slot): the implicated request is
        failed terminally and the engine keeps serving the rest."""
        try:
            if self.prefill_chunk is None:
                while not st.done:
                    self._run_chunk(st)
            else:
                self._run_chunk(st)
            if st.done:
                self._prefills.remove(st)
                staged.append(self._complete_prefill(st))
        except Exception as e:
            if not self.fault_tolerant:
                raise
            # the staging rows were donated into the raising dispatch —
            # this prefill's state is unrecoverable, the engine's isn't
            self._abort_prefill(st, "failed", f"prefill fault: {e!r}")
            if self.prefill_chunk is not None:
                self._subsystem_fault("chunked_prefill", e)
            else:
                self.metrics.on_fault("prefill", repr(e),
                                      step=self._step_in_flight)

    def _advance_prefills(self) -> int:
        """Run this step's prefill work.  Without chunking every pending
        prefill completes (the legacy admit-then-decode shape); with
        ``prefill_chunk`` set, exactly ONE chunk runs per step, so the
        per-step decode stall is bounded by one chunk regardless of how
        long the admitted prompt is.  Completed requests' first tokens
        come back in ONE batched readback.  Returns tokens emitted."""
        staged: List[Tuple[_Prefill, jax.Array]] = []
        try:
            if self.prefill_chunk is None:
                while self._prefills:
                    n = len(self._prefills)
                    self._advance_one(self._prefills[0], staged)
                    if len(self._prefills) >= n:
                        break    # defensive: no progress, stop looping
            elif self._prefills:
                self._advance_one(self._prefills[0], staged)
        finally:
            # even if a later prefill raised, tokens already staged must
            # be emitted — a sampled first token the host forgets would
            # silently desync the request from generate() parity
            emitted = self._flush_staged(staged)
        return emitted

    def _flush_staged(self, staged: List[Tuple[_Prefill, jax.Array]]) -> int:
        """THE batched first-token readback for this step's completed
        prefills, then per request: non-finite containment (fail the
        request, skip the radix insert — the poison must not be cached),
        the deferred prefix-cache insert, and the first-token emit."""
        if not staged:
            return 0
        # a device wait, so a phase of its own: step.prefill is dispatch
        self.metrics.phase("first_token_readback")
        self.metrics.step_count("prefills_completed", len(staged))
        toks = np.asarray(jnp.concatenate([f for _, f in staged]))
        if self._routed:
            # each request's token leads its per-chunk expert counts
            toks = toks.reshape(len(staged), -1)
            for (st, _), counts in zip(staged, toks[:, 1:]):
                for chunk, span in st.chunk_spans:
                    span.attrs["experts_touched"] = int(counts[chunk])
            toks = toks[:, 0]
        emitted = 0
        flush_exc = None
        for (st, _), tok in zip(staged, toks):
            tok = int(tok)
            if tok == NONFINITE_SENTINEL:
                self.metrics.on_fault(
                    "nan_logits", "non-finite logits at prefill "
                    "completion", step=self._step_in_flight)
                self._finalize(st.req, "failed",
                               "non-finite logits at prefill completion")
                continue   # slot reclaimed by _evict_finished this step
            if self._cache_active:
                try:
                    self.prefix_cache.insert(st.req.prompt, self.pool,
                                             st.slot)
                except Exception as e:
                    if not self.fault_tolerant:
                        raise
                    # the insert is an optimization — count the fault
                    # (ladder may bypass the cache) and keep the request
                    self._subsystem_fault("prefix_cache", e)
            # same containment as the decode-harvest loop: these slots
            # were already adopted and their first tokens sampled — a
            # raise for one must not drop the others' first tokens
            try:
                self._emit(self._slots[st.slot], tok, first_token=True)
            except Exception as e:
                self.metrics.on_fault("harvest", repr(e),
                                      step=self._step_in_flight)
                self._finalize(st.req, "failed",
                               f"token emit failed: {e!r}")
                if flush_exc is None:
                    flush_exc = e
                continue
            emitted += 1
        if flush_exc is not None and not self.fault_tolerant:
            raise flush_exc
        return emitted

    # ------------------------------------------------------------ decode
    def _resolve_decode_path(self):
        """Statically resolve the decode implementation for THIS
        engine's shapes: the ``fused_decode`` flag opts into the Pallas
        decode-block kernels, ``decode_block_route`` applies the
        routing policy (flags + measured win region), and the model's
        ``fused_decode_supported`` checks shape/dtype/VMEM legality.
        Under tensor parallelism the fallback chain gains a leg: the
        SHARDED Pallas decode block (``"tp_fused_block"``,
        kernels/decode_block_tp.py — entry/exit ring collectives riding
        the tile dots, in-kernel append on the local slab shard)
        engages when the flag opts in, ``collective_fusion`` is on (its
        rings ARE the fused collectives) and
        ``resolve_fused_decode(tp=...)`` passes the real legality
        (kv_heads/batch/ffn tiling, head alignment, per-shard VMEM
        plan); otherwise the composed compute-collective shard_map
        program (``"tp_fused"``, serving/tp.py) when legal, the
        composed GSPMD decode last — every rung keeps serving.  Returns
        ``(path, fallback_reason)``; reason is None when a fused-block
        path engages (or the flag is simply off).

        The SPECULATIVE leg resolves here too, statically:
        ``spec_on``/``spec_fallback_reason`` name why speculation is
        armed or not for this engine shape (never a runtime surprise —
        the per-step room gate and the ladder's ``spec_bypass`` are the
        only dynamic fallbacks, both named in ``decode_path_info``)."""
        from ..kernels.decode_block import resolve_fused_decode
        if self.spec_k == 0:
            self.spec_on = False
            self.spec_fallback_reason = \
                "spec_k=0 (speculation not requested)"
        elif "speculation" in self.model_refusals:
            self.spec_on = False
            self.spec_fallback_reason = self.model_refusals["speculation"]
        elif self.pool.max_seq <= self.spec_k + 1:
            self.spec_on = False
            self.spec_fallback_reason = (
                f"max_seq {self.pool.max_seq} leaves no room for a "
                f"spec_k={self.spec_k} verify window")
        else:
            self.spec_on = True
            self.spec_fallback_reason = None
        if self.tensor_parallel > 1:
            reason = None
            if self.fused_decode:
                ok, reason = resolve_fused_decode(
                    self.model, batch=self.num_slots,
                    kv_len=self.pool.max_seq, tp=self.tensor_parallel)
                if ok and not self.collective_fusion:
                    ok, reason = False, ("collective_fusion disabled "
                                         "(the sharded block's "
                                         "entry/exit rings are fused "
                                         "collectives)")
                if ok:
                    self.tp_fusion_reason = None
                    return "tp_fused_block", None
            from . import tp as _tp
            ok, tp_reason = _tp.tp_decode_supported(
                self.model, self.tensor_parallel, self.num_slots) \
                if self.collective_fusion \
                else (False, "collective_fusion disabled")
            self.tp_fusion_reason = None if ok else tp_reason
            return ("tp_fused" if ok else "unfused"), reason
        if not self.fused_decode:
            return "unfused", None
        ok, reason = resolve_fused_decode(self.model,
                                          batch=self.num_slots,
                                          kv_len=self.pool.max_seq)
        return ("fused", None) if ok else ("unfused", reason)

    def attention_route(self):
        """``(route, reason)`` of the decode program's attention
        (``kernels.decode_attention.decode_attention_route``): static
        per compiled program, a function of the slot slabs' shape and
        dtype as the program's trace sees them — each device's heads
        inside the ``tp_fused`` shard_map, the whole slab under the
        serving mesh in the composed GSPMD program (where XLA cannot
        partition a Mosaic call).  The fused blocks stream the slab in
        place by construction."""
        from ..kernels.decode_attention import decode_attention_route
        if self.decode_path in ("fused", "tp_fused_block"):
            return "slab_in_place", None
        own = getattr(self.model, "attention_route", None)
        if own is not None:
            # a cache of another row kind has kernels of its own
            return own(self.pool.ks[0].shape, self.pool.ks[0].dtype)
        cfg = self.model.cfg
        slots, rows, slab_heads, dh = self.pool.ks[0].shape
        manual = self.decode_path == "tp_fused"
        tp = self.tensor_parallel if manual else 1
        kv_heads = getattr(cfg, "kv_heads", None) or cfg.num_heads
        with contextlib.nullcontext() if manual else self._mesh_scope():
            return decode_attention_route(
                (slots, 1, cfg.num_heads // tp, dh),
                (slots, rows, slab_heads // tp, dh),
                self.pool.ks[0].dtype, kv_heads // tp)

    def kv_append(self):
        """``(how, reason)`` the decode program writes a step's fresh K
        and V rows into the slot slabs: ``("in_kernel", None)`` where
        its attention kernel does (one DMA of each per slot:
        ``decode_attention.append_and_attend`` on the ``slab_in_place``
        route, the fused blocks by construction), else
        ``("xla_scatter", why)`` with the attention route's own reason
        (``kv_cache.append_kv`` ahead of the attention)."""
        route, why = self.attention_route()
        if route in ("slab_in_place", "latent_in_place"):
            return "in_kernel", None
        return "xla_scatter", why

    def scan_route(self):
        """``(route, reason)`` of the model's recurrence over positions,
        ``("", None)`` for a model without one: which form runs it in
        the prefill programs (at the widest chunk) and in the decode
        program, as the model says (``model.recurrence_route(width)``,
        static per compiled program like :meth:`attention_route`), as
        ``"prefill=<form>,decode=<form>"``."""
        route_of = getattr(self.model, "recurrence_route", None)
        if route_of is None:
            return "", None
        chunk, why = route_of(self._widest_chunk())
        step, _ = route_of(1)
        return f"prefill={chunk},decode={step}", why

    def _widest_chunk(self) -> int:
        return self.prefill_chunk or max(self._warm_buckets or (1,))

    def expert_route(self):
        """``(route, reason)`` of a routing model's grouped matmul over
        its experts, ``("", None)`` for a model without expert layers:
        which form runs in the prefill programs (at the widest chunk)
        and in the decode program, as the model says
        (``model.expert_route(rows)``, static per compiled program), as
        ``"prefill=<form>,decode=<form>"``; the reason is why the
        decode program's is not the kernel."""
        route_of = getattr(self.model, "expert_route", None)
        if route_of is None:
            return "", None
        chunk, _ = route_of(self._widest_chunk())
        step, why = route_of(self.num_slots)
        return f"prefill={chunk},decode={step}", why

    def prefill_attention_route(self):
        """``(route, reason)`` of the prefill programs' attention over a
        request's staging at the widest chunk, ``("", None)`` for a
        model that declares none (``model.chunk_attention_route``,
        static per compiled program like :meth:`attention_route`)."""
        route_of = getattr(self.model, "chunk_attention_route", None)
        if route_of is None:
            return "", None
        return route_of(self._staging_shape(), self._widest_chunk(),
                        self.pool.ks[0].dtype)

    def _staging_shape(self):
        """One request's staging rows of one layer, as the prefill
        programs see them: a slot slab's shape for ONE row."""
        return (1,) + tuple(self.pool.ks[0].shape[1:])

    def expert_load(self):
        """Rows each expert got since the engine was built, ``[expert
        layers, experts]`` (decode steps and prefill chunks, live rows
        only), read from the device HERE; None for a model without
        expert layers."""
        if self._expert_load is None:
            return None
        return np.asarray(self._expert_load)

    def overlap(self):
        """``(overlap, why not one_ahead)``: whether a step dispatches
        its decode program BEFORE it reads the previous one's tokens
        (``one_ahead``) or reads every program in the step that
        dispatched it (``none``).  Decided by what the engine can see,
        each step, in the ONE step body: the next dispatch of a
        speculating engine needs this step's tokens on the host (the
        drafts come from the n-gram tables the harvest feeds, and the
        host picks verify or decode by them), so it reads first; once
        the ladder bypasses speculation it runs ahead like every other
        engine.  A static ``allowed_tokens`` mask needs no token.  Every
        row of the recovery matrix (docs/serving.md) holds one program
        ahead, so the fault configuration forces nothing here."""
        if self.spec_on and not self.spec_bypass:
            return "none", "speculation"
        return "one_ahead", None

    def _emit_decode_block(self) -> None:
        """The discrete obs event that marks WHICH path this engine's
        single decode program compiled with (and why, on fallback) —
        traces distinguish fused from unfused steps without diffing
        configs; the tp dimension separates the sharded block from the
        tp=1 pair in a shared registry; the attention route says
        whether the program reads the slot slabs where they lie, and
        ``kv_append`` whether its kernel also writes them
        (glossary: docs/observability.md)."""
        route, why = self.attention_route()
        append, append_why = self.kv_append()
        scan, scan_why = self.scan_route()
        expert, expert_why = self.expert_route()
        prefill, prefill_why = self.prefill_attention_route()
        overlap, overlap_why = self.overlap()
        self.metrics.on_decode_block(
            active=self.decode_path in ("fused", "tp_fused_block"),
            reason=None if not self.fused_decode
            else self.decode_fallback_reason,
            step=self._step_in_flight,
            tp=self.tensor_parallel,
            attention_route=route, attention_reason=why,
            kv_append=append, kv_append_reason=append_why,
            scan_route=scan, scan_reason=scan_why,
            expert_route=expert, expert_reason=expert_why,
            prefill_attention_route=prefill,
            prefill_attention_reason=prefill_why,
            overlap=overlap, overlap_reason=overlap_why)

    def _build_decode_fn(self) -> Callable:
        model, stateful = self.model, self._stateful
        fused = self.decode_path == "fused"
        self._emit_decode_block()
        if self.decode_path in ("tp_fused", "tp_fused_block"):
            return self._build_tp_decode_fn()

        params, buffers = self._model_weights()

        routed = self._routed

        def decode(params, ks, vs, seq_pos, last_tok, keys, do_sample,
                   temperature, top_k, top_p, mask, state=None, load=None):
            self.trace_counts["decode"] += 1  # trace-time side effect
            caches = [(k, v, seq_pos) for k, v in zip(ks, vs)]
            step_fn = model.fused_decode_step if fused else \
                model.decode_step
            with bind_state(model, params, buffers):
                if routed:
                    # a parked slot's ride-along token is ROUTED to no
                    # expert: where the experts are most of the bytes a
                    # step reads, 24 parked rows of 32 would touch 2.5
                    # times the experts the 8 live ones need
                    logits, caches, rows = step_fn(
                        last_tok[:, None], caches, seq_pos,
                        valid=(seq_pos > 0).astype(jnp.int32))
                elif not stateful:
                    logits, caches = step_fn(last_tok[:, None], caches,
                                             seq_pos)
                else:
                    # every slot's row advances, parked ones included
                    logits, caches, state = step_fn(
                        last_tok[:, None], caches, seq_pos, state=state)
            # the program's tail is its ``sampling`` part
            # (obs/parts.py): the draw, the finite check, the positions
            # and the counters that ride the readback
            with jax.named_scope("sampling"):
                split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                nxt = sample_rows(split[:, 1], logits[:, 0], do_sample,
                                  temperature, top_k, top_p, mask=mask)
                # device-side health probe: a poisoned row reads back as
                # the sentinel through the step's EXISTING single
                # readback (a no-op on finite logits, so token parity is
                # untouched)
                nxt = finite_or_sentinel(logits[:, 0], nxt)
                new_ks = [c[0] for c in caches]
                new_vs = [c[1] for c in caches]
                out = (new_ks, new_vs,
                       _advance_live(seq_pos, caches[0][2]),
                       nxt.astype(jnp.int32), split[:, 0])
                if routed:
                    # what the step's ONE readback carries behind the
                    # tokens: experts that got a live row (over the
                    # expert layers) and the fullest expert's rows
                    counts = jnp.stack([jnp.count_nonzero(rows),
                                        jnp.max(rows)]).astype(jnp.int32)
                    return out + (jnp.concatenate([out[3], counts]),
                                  load + rows)
            return out + (state,) if stateful else out

        # donating the KV slabs (and the recurrent state, where the
        # model has one) aliases them in place — pool memory stays a
        # single allocation across the whole serving run
        donate = (1, 2, 11) if stateful else \
            (1, 2, 12) if routed else (1, 2)
        return functools.partial(
            jax.jit(decode, donate_argnums=donate), params)

    def _build_tp_decode_fn(self) -> Callable:
        """The tensor-parallel fused compute-collective decode: ONE
        shard_map program (serving/tp.py) whose entry all-gathers ride
        the QKV/MLP-up dots and whose exit reduce-scatters ride the
        out-proj/MLP-down dots, then the SAME per-slot sampling tail as
        the composed path on the vocab-sharded logits (GSPMD partitions
        the argmax/top-k reductions).  On the ``tp_fused_block`` path
        the same program's layer bodies run the sharded Pallas
        decode-block kernels instead (kernels/decode_block_tp.py) —
        same signature, same donation, same single compiled decode
        program either way, so the compile-count pin is untouched.  The
        weight bundle survives quarantine rebuilds (it is never
        donated), so a rebuilt plane reuses it; a degradation-ladder
        path change invalidates the cached program (it is path-
        specific)."""
        from . import tp as _tp
        if self._tp_program is None \
                or self._tp_program_path != self.decode_path:
            self._tp_program = _tp.build_tp_decode_program(
                self.model, self.mesh, self.tensor_parallel,
                pallas_block=self.decode_path == "tp_fused_block",
                batch=self.num_slots, max_seq=self.pool.max_seq)
            self._tp_program_path = self.decode_path
        program, weights = self._tp_program

        def decode(weights, ks, vs, seq_pos, last_tok, keys, do_sample,
                   temperature, top_k, top_p, mask):
            self.trace_counts["decode"] += 1  # trace-time side effect
            logits, new_ks, new_vs, new_pos = program(
                weights, ks, vs, seq_pos, last_tok)
            with jax.named_scope("sampling"):
                split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                lg = logits[:, 0]
                nxt = sample_rows(split[:, 1], lg, do_sample,
                                  temperature, top_k, top_p, mask=mask)
                nxt = finite_or_sentinel(lg, nxt)
                return (new_ks, new_vs, _advance_live(seq_pos, new_pos),
                        nxt.astype(jnp.int32), split[:, 0])

        return functools.partial(
            jax.jit(decode, donate_argnums=(1, 2)), weights)

    def _decode_dispatch(self) -> jax.Array:
        """ONE fixed-shape decode step over every slot; returns the
        sampled token vector STILL ON DEVICE — the caller performs the
        step's single host readback (step() times dispatch and readback
        as separate timeline phases)."""
        if self._decode_fn is None:
            # a degradation-ladder path change dropped the handle: try
            # the store's artifact for the NEW path first (a miss is a
            # recorded degradation event), trace only when it has none
            if self.aot_store is not None \
                    and self.aot_status not in (None, "skew"):
                self._decode_fn = self._aot_load(
                    f"decode:{self.decode_path}", donate=(0, 1))
            if self._decode_fn is None:
                self._decode_fn = self._build_decode_fn()
                self._decode_fn = self._with_parts(self._decode_fn)
        if self._sampling_dev is None:
            self._sampling_dev = (jnp.asarray(self._do_sample),
                                  jnp.asarray(self._temperature),
                                  jnp.asarray(self._top_k),
                                  jnp.asarray(self._top_p))
        if self._mask_dev is None:
            self._mask_dev = jnp.asarray(self._mask_host)
        if self._stateful:
            ks, vs, pos, nxt, self._keys, self.pool.state = \
                self._decode_fn(
                    self.pool.ks, self.pool.vs, self.pool.seq_pos,
                    self._last_tok, self._keys, *self._sampling_dev,
                    self._mask_dev, self.pool.state)
        elif self._routed:
            # ``back``: the tokens with the step's expert counts behind
            # them, what the step reads back in place of the tokens
            ks, vs, pos, nxt, self._keys, back, self._expert_load = \
                self._decode_fn(
                    self.pool.ks, self.pool.vs, self.pool.seq_pos,
                    self._last_tok, self._keys, *self._sampling_dev,
                    self._mask_dev, None, self._expert_load)
        else:
            ks, vs, pos, nxt, self._keys = self._decode_fn(
                self.pool.ks, self.pool.vs, self.pool.seq_pos,
                self._last_tok, self._keys, *self._sampling_dev,
                self._mask_dev)
        self.pool.ks, self.pool.vs, self.pool.seq_pos = ks, vs, pos
        self._last_tok = nxt
        return back if self._routed else nxt

    # ----------------------------------------- speculative decode (spec)
    def _build_verify_fn(self) -> Callable:
        """The ONE batched verify program of the speculative path
        (docs/serving.md "Speculative decoding"): fixed shapes
        ``[num_slots, spec_k+1]`` regardless of per-slot acceptance.

        The window runs ``model.decode_step`` at token width
        ``spec_k+1`` with per-slot positions — the SAME ragged
        discipline as decode (``cache_lens`` gives query t of a slot's
        window visibility up to ``pos+t``), so free and mid-prefill
        rows ride along as no-ops exactly as they do in decode.
        Acceptance is MATCHED SAMPLING (``_verify_tail``): the program
        replays the exact per-token split/sample chain sequential
        decode would run over these logits, so the committed tokens ARE
        the sequential target's tokens — token-for-token parity, greedy
        and seeded, is structural rather than probabilistic.  KV of
        rejected positions is written (fixed shapes) but never becomes
        visible: ``seq_pos`` advances only by accepted+1, and the next
        append overwrites the stale tail."""
        model = self.model
        if self.decode_path in ("tp_fused", "tp_fused_block"):
            return self._build_tp_verify_fn()

        params, buffers = self._model_weights()

        def verify(params, ks, vs, seq_pos, last_tok, keys, do_sample,
                   temperature, top_k, top_p, mask, drafts, draft_len):
            self.trace_counts["verify"] += 1  # trace-time side effect
            caches = [(k, v, seq_pos) for k, v in zip(ks, vs)]
            ids = jnp.concatenate([last_tok[:, None], drafts], axis=1)
            with bind_state(model, params, buffers):
                logits, caches = model.decode_step(ids, caches, seq_pos)
            committed, accepted, new_keys = _verify_tail(
                logits, drafts, draft_len, keys, do_sample, temperature,
                top_k, top_p, mask, self.spec_k)
            new_last = jnp.take_along_axis(
                committed, accepted[:, None], axis=1)[:, 0]
            # the caches advanced the full window width — the ragged
            # truth is accepted+1, which also re-hides rejected KV
            new_pos = _advance_live(seq_pos, seq_pos + accepted + 1)
            packed = jnp.concatenate([committed, accepted[:, None]],
                                     axis=1)
            new_ks = [c[0] for c in caches]
            new_vs = [c[1] for c in caches]
            return (new_ks, new_vs, new_pos,
                    new_last.astype(jnp.int32), packed, new_keys)

        return functools.partial(
            jax.jit(verify, donate_argnums=(1, 2)), params)

    def _build_tp_verify_fn(self) -> Callable:
        """Tensor-parallel fused verify: the width-``spec_k+1`` member
        of the SAME shard_map family as the fused decode
        (tp.build_tp_verify_program — identical bundle layout and
        specs, the layer seam IS ``_tp_layer``), with the matched-
        sampling acceptance tail under GSPMD on the vocab-sharded
        logits inside the same jit.  The ``tp_fused_block`` path
        verifies through this program too (the Pallas block is a
        single-token kernel) and keeps its block for decode steps."""
        from . import tp as _tp
        if self._tp_verify_program is None \
                or self._tp_verify_program_path != self.decode_path:
            self._tp_verify_program = _tp.build_tp_verify_program(
                self.model, self.mesh, self.tensor_parallel,
                width=self.spec_k + 1)
            self._tp_verify_program_path = self.decode_path
        program, weights = self._tp_verify_program

        def verify(weights, ks, vs, seq_pos, last_tok, keys, do_sample,
                   temperature, top_k, top_p, mask, drafts, draft_len):
            self.trace_counts["verify"] += 1  # trace-time side effect
            ids = jnp.concatenate([last_tok[:, None], drafts], axis=1)
            logits, new_ks, new_vs, _ = program(weights, ks, vs, seq_pos,
                                                ids)
            committed, accepted, new_keys = _verify_tail(
                logits, drafts, draft_len, keys, do_sample, temperature,
                top_k, top_p, mask, self.spec_k)
            new_last = jnp.take_along_axis(
                committed, accepted[:, None], axis=1)[:, 0]
            new_pos = _advance_live(seq_pos, seq_pos + accepted + 1)
            packed = jnp.concatenate([committed, accepted[:, None]],
                                     axis=1)
            return (new_ks, new_vs, new_pos,
                    new_last.astype(jnp.int32), packed, new_keys)

        return functools.partial(
            jax.jit(verify, donate_argnums=(1, 2)), weights)

    def _verify_dispatch(self, drafts: np.ndarray,
                         draft_len: np.ndarray) -> jax.Array:
        """ONE fixed-shape verify step over every slot; returns the
        packed ``[num_slots, spec_k+2]`` commit rows (each slot's
        sentinel-encoded window samples + its accepted draft length)
        STILL ON DEVICE — the caller performs the step's single host
        readback, exactly like decode."""
        if self._verify_fn is None:
            if self.aot_store is not None \
                    and self.aot_status not in (None, "skew"):
                self._verify_fn = self._aot_load(
                    f"verify:{self.decode_path}", donate=(0, 1))
            if self._verify_fn is None:
                self._verify_fn = self._build_verify_fn()
        if self._sampling_dev is None:
            self._sampling_dev = (jnp.asarray(self._do_sample),
                                  jnp.asarray(self._temperature),
                                  jnp.asarray(self._top_k),
                                  jnp.asarray(self._top_p))
        if self._mask_dev is None:
            self._mask_dev = jnp.asarray(self._mask_host)
        ks, vs, pos, nxt, packed, self._keys = self._verify_fn(
            self.pool.ks, self.pool.vs, self.pool.seq_pos,
            self._last_tok, self._keys, *self._sampling_dev,
            self._mask_dev, jnp.asarray(drafts), jnp.asarray(draft_len))
        self.pool.ks, self.pool.vs, self.pool.seq_pos = ks, vs, pos
        self._last_tok = nxt
        return packed

    def _propose_drafts(self):
        """Host draft phase: ask every active slot's n-gram table for up
        to ``spec_k`` tokens.  Returns ``(drafts [num_slots, spec_k],
        draft_len [num_slots], total_drafted)`` or None when this step
        should run the normal decode instead — speculation off/bypassed,
        nothing proposed anywhere, or ANY occupied slot within
        ``spec_k+1`` rows of its row end (``append_kv`` clamps a
        window's start at ``max_seq - width``, which would overwrite
        that row's valid KV — the whole step falls back rather than
        corrupt it; such a slot is about to hit max_seq anyway)."""
        if not self.spec_on or self.spec_bypass or not self._slots:
            return None
        k = self.spec_k
        limit = self.pool.max_seq - k - 1
        drafts = np.zeros((self.num_slots, k), np.int32)
        lens = np.zeros((self.num_slots,), np.int32)
        total = 0
        for slot, st in self._slots.items():
            if st.pos > limit:
                return None
            if st.draft is None or st.req.finished:
                continue
            toks = st.draft.propose(k, allowed=st.allowed)
            if toks:
                drafts[slot, :len(toks)] = toks
                lens[slot] = len(toks)
                total += len(toks)
        if total == 0:
            return None
        return drafts, lens, total

    # ------------------------------------------- one program in flight
    def _park_ending(self) -> Dict[int, _Slot]:
        """The slots the step's decode program runs LIVE, ``{slot:
        _Slot}``.  A slot whose request will end by LENGTH with the
        token still on the device (the host knows that without the
        token) is parked first, ahead of its release, and rides the
        program as any parked slot does; submit() holds ``prompt_len +
        max_new_tokens`` within ``max_seq``, so the same rule keeps a
        row one short of its end from being run past it.  An ``eos`` is
        known only at harvest: that slot runs once more (an OVERRUN row,
        ``_harvest_program``).  The request stays placed, and counted in
        flight, until its last token has been emitted."""
        live = {}
        for slot, st in self._slots.items():
            if not st.parked and st.pending and len(st.req.tokens) \
                    + st.pending >= st.req.max_new_tokens:
                self.pool.park(slot)
                st.parked = True
            if not st.parked:
                live[slot] = st
        return live

    def _dispatch(self, live: Dict[int, _Slot], spec,
                  spans) -> None:
        """Dispatch the step's decode program (the verify program where
        ``spec`` holds drafts) over ``live`` and put it in flight with
        what its harvest will need; the token vector's transfer to the
        host starts as soon as the program ends, whoever waits for it.
        The step's span describes THIS program: its counts are taken
        here, and what arrives with its tokens joins them at the
        harvest (``_InFlight.step``)."""
        counts = spans.counts
        counts["active_slots"] = len(live)
        counts["loop_passes"] = self.loop_passes
        # slots whose recurrent state the program reads and writes: all
        # of them, parked ones included
        counts["state_slots"] = self.num_slots if self._stateful else 0
        # free slots read False in the mirror, so this counts occupied
        # slots only; > 0 exactly when the program's sampling branch
        # runs
        counts["sampling_slots"] = int(np.count_nonzero(self._do_sample))
        # the rows the device holds: one more than the host has
        # harvested for a slot whose last token is still unread
        counts["live_kv_rows"] = sum(st.pos + st.pending
                                     for st in live.values())
        # 1 where an earlier program's tokens are still unread: this one
        # starts behind it with no host in between
        counts["decode_ahead"] = int(bool(self._inflight))
        drafted = None
        if spec is not None:
            drafts, draft_len, drafted = spec
            toks = self._verify_dispatch(drafts, draft_len)
        else:
            toks = self._decode_dispatch()
        # a request that finished at admission (eos or length on its
        # first token) rides live until this step's eviction: its row
        # is nobody's
        owners = {slot: st for slot, st in live.items()
                  if not st.req.finished}
        self._inflight.append(_InFlight(toks, owners, spans, drafted))
        for st in owners.values():
            st.pending += 1
        toks.copy_to_host_async()

    def _harvest_program(self, handle: _InFlight, toks) -> int:
        """Hand one read program's tokens to the requests it was
        dispatched for; returns the tokens emitted.  A row whose request
        ended before the read (an ``eos`` found one program late, a
        cancel or a deadline in between) is dropped: it reaches no
        ``req.tokens``, stream, journal record or draft table."""
        metrics = self.metrics
        verify = handle.drafted is not None
        if self._routed and not verify:
            metrics.late_step_counts(
                handle.step,
                experts_touched=int(toks[self.num_slots]),
                expert_rows_max=int(toks[self.num_slots + 1]))
        # the program already advanced EVERY slot's device state: a
        # raise mid-loop (a user stream callback, an emit bug) must not
        # drop the LATER slots' tokens — on the watchdog's retry they
        # would silently skip one token and desync from generate()
        # parity.  Finish the loop, fail the implicated request,
        # re-raise only outside the watchdog (inside it the containment
        # is already complete — no retry needed).
        harvest_exc = None
        emitted = accepted_total = overrun = 0
        for slot in sorted(handle.owners):
            st = handle.owners[slot]
            st.pending -= 1
            if st.req.finished:
                # ended since the dispatch (a stream callback may also
                # REENTRANTLY cancel a sibling mid-loop)
                overrun += 1
                continue
            try:
                if not verify:
                    emitted += self._harvest(st, slot, int(toks[slot]))
                else:
                    a = int(toks[slot, self.spec_k + 1])
                    accepted_total += a
                    emitted += self._harvest_window(
                        st, slot, toks[slot, :a + 1])
            except Exception as e:
                metrics.on_fault("harvest", repr(e),
                                 step=self._step_in_flight)
                self._finalize(st.req, "failed",
                               f"token emit failed: {e!r}")
                if harvest_exc is None:
                    harvest_exc = e
        if overrun:
            metrics.on_overrun(overrun)
        if verify:
            metrics.on_spec(handle.drafted, accepted_total)
        if harvest_exc is not None and not self.fault_tolerant:
            raise harvest_exc
        return emitted

    # -------------------------------------------------------- step loop
    def step(self) -> int:
        """One engine iteration: admit (radix match + staging), advance
        prefill chunks, dispatch one decode step over all live slots,
        read and harvest the tokens of the program in flight (the
        previous step's, one program ahead; this step's, otherwise),
        evict finished.  Returns the number of requests still in flight
        (prefilling + running + queued), non-zero while a dispatched
        program is unread.

        With ``fault_tolerance`` configured this is the WATCHDOG
        boundary: a step exception is caught, attributed (optional
        subsystem → degradation ladder; core → bounded exponential-
        backoff retry → quarantine rebuild), and never propagates — the
        recovery matrix is in docs/serving.md.  Without the config the
        engine raises exactly as before."""
        if not self.fault_tolerant:
            return self._step_impl()
        if self.health.circuit_open:
            # fail-fast mode: the breaker already failed all work and
            # submit() rejects — stepping is a no-op, never a rebuild
            return self._work_left()
        try:
            out = self._step_impl()
        except Exception as e:
            return self._on_step_fault(e)
        self.health.on_step_ok()
        self._publish_health()
        return out

    def _step_impl(self) -> int:
        """``_step_body`` inside the mesh scope when tensor-parallel:
        the engine's jitted programs trace their bare-PartitionSpec
        sharding constraints (the models' ``_maybe_constraint`` calls)
        against the serving mesh, so GSPMD partitions every program the
        step dispatches.  Single-chip engines skip the push entirely."""
        if self.mesh is None:
            return self._step_body()
        with jax.set_mesh(self.mesh):
            return self._step_body()

    def _step_body(self) -> int:
        """The raw step.  Every part of it runs inside a live
        ``step.<phase>`` span (``metrics.STEP_PHASES``), a child of the
        step's ``serving.step`` span on the engine lane; the children
        tile the step, each feeds a ``serving.phase.<name>_s`` histogram,
        and with ``record_events=True`` each is a profiler annotation
        too.  The step span carries the step's counts
        (``metrics.STEP_COUNTS``); trace-counter deltas / head-of-line
        skips / evictions become discrete events.  The per-slot token
        readback stays the step's ONLY device sync beside a completed
        prefill's first tokens; one program ahead it waits for the
        program the PREVIOUS step dispatched while this step's runs
        behind it (``_InFlight``, ``overlap``)."""
        t0 = time.perf_counter()
        metrics = self.metrics
        step_i = self._step_index
        self._step_index += 1
        self._step_in_flight = step_i
        self._fault_phase = None
        skips_before = self.scheduler.total_head_skips
        faults = self.faults
        if faults is not None:
            armed = faults.check("slow_step")
            if armed is not None:
                metrics.on_fault(
                    "slow_step", f"injected {armed.seconds}s stall",
                    step=step_i)
                time.sleep(armed.seconds)
        new_tokens = 0
        spans = metrics.begin_step(step_i, "admission")
        try:
            if self._deadlines_possible:
                self._expire_deadlines(time.perf_counter())
            admitted = self.scheduler.admit(
                self.pool.free_slots,
                token_budget=self.max_prefill_tokens_per_step,
                cost=self._prefill_cost)
            for i, (req, _) in enumerate(admitted):
                try:
                    self._begin_prefill(req)
                except BaseException:
                    # admission failure must not LOSE requests: the
                    # failing one and the rest of the popped batch go
                    # back to the queue head (their slots/pins were
                    # already returned)
                    self.scheduler.requeue_front(
                        [r for r, _ in admitted[i:]])
                    raise
            counts = spans.counts
            counts["admitted"] = len(admitted)
            counts["queue_depth"] = self.scheduler.queue_depth
            # step.prefill is dispatch only: _flush_staged moves on to
            # step.first_token_readback where a prefill completed
            metrics.phase("prefill")
            new_tokens = self._advance_prefills()
            live = self._park_ending()
            dispatched = False
            if live:
                # speculative draft phase (pure host, spec_on only):
                # None -> normal decode this step, else the batched
                # fixed-shape verify program commits up to spec_k+1
                # tokens per slot
                spec = None
                if self.spec_on:
                    metrics.phase("draft")
                    spec = self._propose_drafts()
                metrics.phase("decode_dispatch")
                if faults is not None:
                    armed = faults.check("nan_logits")
                    if armed is not None:
                        self._poison_slot(min(live), step_i)
                # decode faults cannot be pinned on one slot — the
                # watchdog attributes them to the decode path (ladder
                # candidate when fused or speculating, retry/quarantine
                # otherwise)
                if spec is not None:
                    self._fault_phase = "spec_verify"
                else:
                    self._fault_phase = "fused_decode" \
                        if self.decode_path in ("fused",
                                                "tp_fused_block") \
                        else "decode"
                if faults is not None:
                    faults.fire("step")
                    if spec is not None:
                        # fires BEFORE dispatch: nothing was mutated
                        # yet, so the ladder's retry step is clean
                        faults.fire("spec_verify")
                self._dispatch(live, spec, spans)
                dispatched = True
            # the programs this step leaves unread: the one it just
            # dispatched, where the next dispatch can go without its
            # tokens; none where it dispatched nothing (the last
            # program drains) or reads before it dispatches
            keep = int(dispatched and self.overlap()[0] == "one_ahead")
            while len(self._inflight) > keep:
                metrics.phase("readback")
                # THE per-step device readback; a raise leaves the
                # program in flight for the retried step to read
                toks = np.asarray(self._inflight[0].toks)
                handle = self._inflight.popleft()
                metrics.phase("harvest")
                new_tokens += self._harvest_program(handle, toks)
            # what follows is the core's: a fault in it is no decode
            # path's (per-slot harvest faults were contained above)
            self._fault_phase = None
            metrics.phase("bookkeeping")
            self._evict_finished()
            if self.journal is not None:
                self._journal_progress()
        finally:
            # a raised step must still close its open phase and then the
            # step span (and their trace annotations, innermost first),
            # or every later span nests inside a phantom serving.step
            # (resource-lifecycle rule: begin_step/end_step)
            spans.counts["new_tokens"] = new_tokens
            metrics.end_step(spans)
        # the telemetry's own accounting runs outside the step span, so
        # that it does not time itself
        self._record_events(step_i, skips_before)
        metrics.record_step(
            active_slots=len(self._slots), num_slots=self.num_slots,
            queue_depth=self.scheduler.queue_depth,
            new_tokens=new_tokens,
            step_seconds=time.perf_counter() - t0,
            phases=spans.phases)
        return self._work_left()

    def _journal_progress(self) -> None:
        """Batch this step's delivered high-water marks into ONE journal
        record (host ints the harvest loop already produced — nothing
        here touches the device).  Runs at the end of the step, after
        eviction, so a request that finished this step is covered by its
        terminal record instead."""
        updates = {}
        for st in self._slots.values():
            rid, n = st.req.request_id, len(st.req.tokens)
            if n and self._journal_hwm.get(rid) != n:
                updates[rid] = self._journal_hwm[rid] = n
        self.journal.append_progress(updates)

    def _poison_slot(self, slot: int, step_i: int) -> None:
        """Chaos-only: overwrite position 0 of ``slot``'s layer-0 K row
        with NaN.  Decode attention propagates it into that slot's
        logits, the in-program finiteness probe encodes the sentinel,
        and the harvest fails exactly the implicated request — the
        honest end-to-end drive of the non-finite recovery path (the
        poisoned position is re-written wholesale by the next adopt)."""
        self.pool.ks[0] = self.pool.ks[0].at[slot, 0].set(jnp.nan)
        self.metrics.on_fault("nan_logits",
                              f"injected NaN into slot {slot} KV",
                              step=step_i)

    # ---------------------------------------------- watchdog / recovery
    def _publish_health(self) -> None:
        self.health.degraded = self.ladder.level > 0
        self.metrics.on_health_state(self.health.state,
                                     self.health.state_code,
                                     step=self._step_in_flight)

    def _on_step_fault(self, exc: Exception) -> int:
        """A step raised under the watchdog.  Attribution decides the
        response: a fault in the fused decode path feeds the ladder
        (composed decode is the always-available fallback); anything
        else consumes one retry from the backoff budget, and a spent
        budget quarantines.  State was already unwound by the step's own
        exception handling (admission requeues its batch, prefill faults
        abort their request), so 'retry' simply means the next step()
        runs normally after the backoff sleep."""
        step_i = self._step_in_flight
        phase = self._fault_phase or "step"
        if phase == "spec_verify" and self.spec_on \
                and not self.spec_bypass:
            # speculation is optional: its faults feed the ladder, which
            # disables it at threshold — decode is always the fallback
            self._subsystem_fault("spec_verify", exc)
        elif phase == "fused_decode" \
                and self.decode_path in ("fused", "tp_fused_block"):
            self._subsystem_fault("fused_decode", exc)
        else:
            self.metrics.on_fault(phase, repr(exc), step=step_i)
            backoff = self.health.record_step_fault(repr(exc))
            if backoff is None:
                self._quarantine(f"{phase} fault: {exc!r}")
            else:
                self.metrics.on_retry(self.health.consecutive_faults,
                                      backoff, step=step_i)
                if backoff > 0:
                    time.sleep(backoff)
        self._publish_health()
        return self._work_left()

    def _subsystem_fault(self, subsystem: str, exc: Exception) -> None:
        """Count one fault against an OPTIONAL subsystem; at the ladder
        threshold the subsystem is disabled and the engine keeps serving
        without it (the fault site already contained the failure)."""
        self.metrics.on_fault(subsystem, repr(exc),
                              step=self._step_in_flight)
        if not self.ladder.disabled(subsystem) \
                and self.ladder.record_fault(subsystem):
            self._disable_subsystem(subsystem, repr(exc))

    def _disable_subsystem(self, subsystem: str, reason: str) -> None:
        """Apply one degradation-ladder rung (docs/serving.md ladder
        table).  Disabling is engine-lifetime — a subsystem that proved
        unreliable is not silently re-armed by a later rebuild."""
        if subsystem == "prefix_cache":
            self.prefix_bypass = True     # matches/inserts stop; live
            # pins release normally as their requests finish
        elif subsystem == "chunked_prefill":
            self.prefill_chunk = None     # whole-bucket prefill; plans
            # already computed keep their compiled chunk widths
        elif subsystem == "fused_decode":
            if self.tensor_parallel > 1:
                # the sharded-block rung degrades to the composed
                # compute-collective program when it is legal, the GSPMD
                # decode otherwise — the same order as the resolve chain
                from . import tp as _tp
                ok, tp_reason = _tp.tp_decode_supported(
                    self.model, self.tensor_parallel, self.num_slots) \
                    if self.collective_fusion \
                    else (False, "collective_fusion disabled")
                self.decode_path = "tp_fused" if ok else "unfused"
                self.tp_fusion_reason = None if ok else tp_reason
            else:
                self.decode_path = "unfused"
            self.decode_fallback_reason = f"degraded: {reason}"
            self._decode_fn = None        # re-trace composed on next use
            self._verify_fn = None        # verify is path-keyed too
        elif subsystem == "spec_verify":
            # back to one committed token per step; the draft tables
            # stay on their slots (pure host state, nothing reads them)
            self.spec_bypass = True
            self.spec_fallback_reason = f"degraded: {reason}"
            self.metrics.on_spec_disable(reason)
        else:
            raise ValueError(f"unknown subsystem {subsystem!r}")
        self.health.degraded = True
        self.metrics.on_degrade(subsystem, self.ladder.level, reason)

    def _quarantine(self, reason: str) -> None:
        """The step-retry budget is spent: fail the implicated in-flight
        requests terminally (their device state may hold donated
        garbage), rebuild the device plane, and leave queued work intact
        for re-serving.  ``enter_quarantine``/``leave_quarantine`` is a
        registered graftlint ``ResourcePair`` — the window closes on
        every path."""
        step_i = self._step_in_flight
        q = self.health.enter_quarantine(reason)
        try:
            self.metrics.on_quarantine("enter", reason, step=step_i)
            now = time.perf_counter()
            for st in list(self._prefills):
                self._abort_prefill(st, "failed", f"quarantine: {reason}")
            for slot in list(self._slots):
                req = self._slots[slot].req
                if not req.finished:
                    self._finalize(req, "failed",
                                   f"quarantine: {reason}", now=now)
                elif req.status is None:
                    # completed normally (eos/length) this very step but
                    # not yet evicted when the fault hit: stamp the
                    # NORMAL terminal accounting — quarantining an
                    # already-finished request must not fail it, nor
                    # leave it terminal with no status at all
                    self._finalize(req, "finished", req.finish_reason,
                                   now=now)
                self._release_slot(slot, now)
            # what the old plane's programs still owe goes with it:
            # every request they ran for was failed above
            self._inflight.clear()
            self._build_device_plane()
            if self.health.circuit_open:
                self._open_circuit(reason)
        finally:
            seconds = self.health.leave_quarantine(q)
            self.metrics.on_quarantine("leave", reason, step=step_i,
                                       seconds=seconds)

    def _open_circuit(self, reason: str) -> None:
        """Too many quarantines inside the breaker window: stop
        flapping.  Everything queued fails terminally (nothing is ever
        silently dropped), submit() rejects with ``circuit_open``, and
        step() becomes a no-op — an operator decision (restart, new
        engine) is required past this point."""
        self.metrics.tracer.event("circuit_open",
                                  lane=self.metrics.engine_lane,
                                  reason=reason[:200],
                                  step=self._step_in_flight)
        while self.scheduler.waiting:
            req = self.scheduler.waiting.popleft()
            self._finalize(req, "failed", f"circuit open: {reason}")

    def _record_events(self, step_i: int, skips_before: int) -> None:
        """Turn this step's discrete happenings into event-log entries:
        trace-counter deltas = program compiles, scheduler skip-counter
        delta = head-of-line jumps (prefix-cache evictions report
        themselves through the ``on_event`` hook as they happen)."""
        tracer = self.metrics.tracer
        counts = dict(self.trace_counts)
        if self.block_pool is not None:
            counts.update({f"block_{k}": v
                           for k, v in self.block_pool.trace_counts.items()})
        for prog, n in counts.items():
            seen = self._compile_seen.get(prog, 0)
            if n > seen:
                self.metrics.on_compile(prog, n - seen)
                tracer.event("compile", lane=self.metrics.engine_lane,
                             program=prog,
                             count=n - seen, step=step_i)
        self._compile_seen = counts
        skips = self.scheduler.total_head_skips
        if skips > skips_before:
            tracer.event("head_of_line_skip",
                         lane=self.metrics.engine_lane,
                         count=skips - skips_before, step=step_i)

    def _emit(self, st: _Slot, tok: int, first_token: bool = False) -> None:
        req = st.req
        req.tokens.append(tok)
        if st.draft is not None:
            # the n-gram draft table learns every COMMITTED token, off
            # the hot path — harvest time, after the step's readback
            st.draft.observe(tok)
        self.progress_counter += 1              # token out = progress
        now = time.perf_counter()
        if first_token:
            req.first_token_time = now
            self.metrics.on_first_token(req.arrival_time, now=now)
            tracer = self.metrics.tracer
            if tracer.enabled:
                lane = self._lane(req)
                tracer.add_span("prefill", lane,
                                req.admit_time or req.arrival_time, now,
                                chunks=req.prefill_chunks,
                                hit_tokens=req.prefix_hit_tokens,
                                loop_passes=self.loop_passes,
                                request=req.request_id,
                                step=self._step_in_flight)
                tracer.event("first_token", lane=lane, t=now)
        elif req.last_token_time is not None:
            self.metrics.on_output_token(now - req.last_token_time)
        req.last_token_time = now
        if req.stream is not None:
            try:
                req.stream(req, tok)
            except Exception as e:
                # the CLIENT's sink broke, not the engine: fail exactly
                # this request (its token is already recorded) and keep
                # serving — a raising callback must never reach the
                # watchdog, where the step retry would silently desync
                # every OTHER slot from the already-advanced device state
                if not self.fault_tolerant:
                    raise
                self.metrics.on_fault("stream", repr(e),
                                      step=self._step_in_flight)
                self._finalize(req, "failed",
                               f"stream callback raised: {e!r}")
                return
        eos = req.eos_token_id
        if eos is not None and tok == eos:
            req.finished, req.finish_reason = True, "eos"
        elif len(req.tokens) >= req.max_new_tokens:
            req.finished, req.finish_reason = True, "length"

    def _harvest(self, st: _Slot, slot: int, tok: int) -> int:
        if tok == NONFINITE_SENTINEL:
            # the in-program finiteness probe tripped for THIS row: fail
            # exactly the implicated request (slot reclaimed by
            # _evict_finished this same step; the poisoned row is
            # overwritten wholesale by the next adopt)
            self.metrics.on_fault("nan_logits",
                                  f"non-finite logits in decode "
                                  f"(slot {slot})",
                                  step=self._step_in_flight)
            self._finalize(st.req, "failed",
                           "non-finite logits in decode")
            return 0
        st.pos += 1
        self._emit(st, tok)
        return 1

    def _harvest_window(self, st: _Slot, slot: int, toks) -> int:
        """Commit one slot's verify window — its accepted draft prefix
        plus the bonus token — through the SAME per-token path as
        sequential decode (:meth:`_harvest`), in order.  The loop
        breaks where the sequential engine would have stopped stepping:
        eos/length finishes, the non-finite sentinel, a reentrant
        cancel.  A truncated tail is simply discarded — the slot is
        evicted this same step, so its device state (which advanced by
        the full accepted length) is never read again."""
        emitted = 0
        for tok in toks:
            if st.req.finished:
                break
            got = self._harvest(st, slot, int(tok))
            if got == 0:
                break              # sentinel failed the request
            emitted += got
        return emitted

    # --------------------------------------------- terminal dispositions
    def _finalize(self, req: Request, status: str, reason: str,
                  now: Optional[float] = None) -> None:
        """Stamp one request's TERMINAL disposition — every submitted
        request passes through here exactly once (normal completions
        arrive from ``_evict_finished``/``_quarantine`` with
        ``status="finished"``), which is what the chaos suite's
        total-accounting invariant pins.  Does NOT touch slots/pins:
        the call site owns whatever unwinding its state demands."""
        if req.finished and req.status is not None:
            return                        # already terminal (idempotent)
        if now is None:
            now = time.perf_counter()
        req.finished = True
        req.status = status
        req.status_reason = reason
        req.finish_time = now
        self.progress_counter += 1        # a disposition is progress
        if status == "finished":
            self.metrics.on_finish()
        else:
            self.metrics.on_terminal(status, reason, req.request_id,
                                     now=now)
        if self.journal is not None:
            # exactly ONE terminal record per request: _finalize's
            # idempotence guard above is the single stamping path
            self._journal_hwm.pop(req.request_id, None)
            self.journal.append_terminal(req.request_id, status, reason,
                                         delivered=len(req.tokens))
        self._close_request_telemetry(req, now)

    def _close_request_telemetry(self, req: Request, now: float) -> None:
        tracer = self.metrics.tracer
        if not tracer.enabled:
            return
        lane = self._lane(req)
        rid = req.request_id
        if req.first_token_time is not None:
            tracer.add_span("decode", lane, req.first_token_time, now,
                            tokens=len(req.tokens), request=rid,
                            step=self._step_in_flight)
        tracer.add_span("request", lane, req.arrival_time, now,
                        tokens=len(req.tokens), request=rid,
                        finish_reason=req.finish_reason or req.status)

    def _release_slot(self, slot: int, now: float) -> Request:
        """Return one occupied slot's resources — scheduler entry, radix
        pin, pool slot, sampling row — in one place, so cancellation,
        deadline expiry, quarantine and normal eviction cannot drift
        apart in what they free."""
        req = self.scheduler.release(slot)
        st = self._slots.pop(slot)
        if st.match is not None:
            # unpin the request's radix path — its blocks become
            # LRU-evictable again (release is idempotent)
            self.prefix_cache.release(st.match)
        self.pool.free(slot)
        self._do_sample[slot] = False
        self._sampling_dev = None
        if not self._mask_host[slot].all():
            self._mask_host[slot] = True      # constrained row retires
            self._mask_dev = None
        tracer = self.metrics.tracer
        if tracer.enabled:
            tracer.event("slot_release", lane=self.metrics.engine_lane,
                         t=now, slot=slot, request=req.request_id,
                         reason=req.status_reason or req.finish_reason)
        return req

    def _abort_prefill(self, st: _Prefill, status: str,
                       reason: str) -> None:
        """Unwind one MID-PREFILL request (cancel / deadline / fault /
        quarantine): drop it from the prefill queue, return its slot and
        radix pin, stamp the terminal status.  The staging rows die with
        the last reference — they were never adopted into the pool."""
        if st in self._prefills:
            self._prefills.remove(st)
        self._slots.pop(st.slot, None)    # defensive: adopt may have run
        if st.match is not None:
            self.prefix_cache.release(st.match)
        self.scheduler.release(st.slot)
        self.pool.free(st.slot)
        self._do_sample[st.slot] = False
        self._sampling_dev = None
        if not self._mask_host[st.slot].all():
            self._mask_host[st.slot] = True
            self._mask_dev = None
        self._finalize(st.req, status, reason)

    def cancel(self, request_id: int, status: str = "cancelled",
               reason: str = "cancelled by client") -> bool:
        """Cleanly unwind one request in ANY state — queued,
        mid-(chunked-)prefill, or decoding — freeing its pool slot,
        staging rows and pinned radix path immediately.  Returns True
        when the request was found in flight (False: unknown id or
        already terminal — cancellation is idempotent)."""
        req = self.scheduler.remove_waiting(request_id)
        if req is not None:
            self._finalize(req, status, reason)
            return True
        for st in list(self._prefills):
            if st.req.request_id == request_id:
                self._abort_prefill(st, status, reason)
                return True
        for slot, sl in list(self._slots.items()):
            if sl.req.request_id == request_id and not sl.req.finished:
                now = time.perf_counter()
                self._finalize(sl.req, status, reason, now=now)
                self._release_slot(slot, now)
                return True
        return False

    def _expire_deadlines(self, now: float) -> None:
        """Host-side per-step deadline sweep (runs only once any
        submitted request has carried a deadline): queued requests whose
        budget is already blown never consume a slot; in-flight ones are
        unwound exactly like a cancel, with status
        ``deadline_exceeded``."""
        for req in self.scheduler.expired_waiting(now):
            self._finalize(req, "deadline_exceeded",
                           req.deadline_violation(now) or
                           "deadline exceeded", now=now)
        for st in list(self._prefills):
            v = st.req.deadline_violation(now)
            if v is not None:
                self._abort_prefill(st, "deadline_exceeded", v)
        for slot, sl in list(self._slots.items()):
            if sl.req.finished:
                continue
            v = sl.req.deadline_violation(now)
            if v is not None:
                self._finalize(sl.req, "deadline_exceeded", v, now=now)
                self._release_slot(slot, now)

    # ------------------------------------------------ submit-time gates
    def check_admission(self, req: Request) -> None:
        """Submit-time backpressure (docs/serving.md): bounded queue,
        SLO-aware rejection when the projected TTFT already exceeds the
        request's deadline, and fail-fast once the circuit is open.
        Raises :class:`RequestRejected` with a live-metrics retry hint;
        on acceptance, just records whether deadline sweeps are needed."""
        if self.fault_tolerant and self.health.circuit_open:
            self._reject(req, "circuit_open", None)
        if self.max_queue is not None \
                and self.scheduler.queue_depth >= self.max_queue:
            excess = self.scheduler.queue_depth - self.max_queue + 1
            self._reject(req, "queue_full",
                         self.metrics.retry_after_hint(excess))
        if req.ttft_deadline_s is not None:
            projected = self.metrics.projected_ttft_s(
                self.scheduler.queue_depth)
            if projected is not None \
                    and projected > req.ttft_deadline_s:
                self._reject(req, "slo_unattainable",
                             self.metrics.retry_after_hint())
        if req.deadline_s is not None or req.ttft_deadline_s is not None:
            self._deadlines_possible = True

    def _reject(self, req: Request, reason: str,
                retry_after_s: Optional[float]) -> None:
        req.finished = True
        req.status = "rejected"
        req.status_reason = reason
        req.finish_time = time.perf_counter()
        self.metrics.on_terminal("rejected", reason, req.request_id)
        raise RequestRejected(reason, retry_after_s)

    def _evict_finished(self) -> None:
        for slot in [s for s, st in self._slots.items() if st.req.finished]:
            now = time.perf_counter()
            req = self._release_slot(slot, now)
            if req.status is None:
                # normal completion (eos/length): abnormal statuses were
                # settled at their _finalize site, this loop reclaims
                self._finalize(req, "finished", req.finish_reason,
                               now=now)

    # ----------------------------------------------------- conveniences
    def has_work(self) -> bool:
        """Whether another ``step()`` has anything to do: a request
        queued or placed, or a dispatched program still unread (its
        request may have ended by ``eos`` since: the read drops the row
        and counts it)."""
        return self.scheduler.has_work() or bool(self._inflight)

    def _work_left(self) -> int:
        """What ``step()`` returns: the requests in flight (a request
        stays placed until its last token has been emitted), and never
        zero while a dispatched program is unread, so that ``while
        eng.step()`` drains it."""
        return self.scheduler.active + self.scheduler.queue_depth \
            or len(self._inflight)

    def stall_snapshot(self) -> Dict[str, object]:
        """Host-state diagnostic attached to
        :class:`~paddle_tpu.serving.errors.EngineStalledError` (and
        useful on its own for operator dumps)."""
        return {
            "queue_depth": self.scheduler.queue_depth,
            "active": self.scheduler.active,
            "mid_prefill": len(self._prefills),
            "free_slots": self.pool.free_slots,
            "free_blocks": None if self.block_pool is None
            else self.block_pool.free_blocks,
            "seq_pos": np.asarray(self.pool.seq_pos).tolist(),
            "health": self.health.state,
            "degraded_subsystems": list(self.ladder.disabled_subsystems),
            "progress_counter": self.progress_counter,
            "programs_in_flight": len(self._inflight),
            "steps": self._step_index,
            "tensor_parallel": self.tensor_parallel,
            "speculation": self.spec_on and not self.spec_bypass,
        }

    def run_until_complete(self, max_steps: Optional[int] = None,
                           stall_steps: Optional[int] = 64) -> int:
        """Step until queue and slots drain; returns steps taken.

        ``stall_steps`` arms the no-progress detector: if that many
        CONSECUTIVE steps emit no token, admit no request, run no
        prefill chunk and settle no request while work is still queued,
        :class:`EngineStalledError` is raised with a diagnostic snapshot
        instead of spinning forever (None disables — the pre-robustness
        behavior)."""
        steps = 0
        stalled = 0
        last_progress = self.progress_counter
        while self.has_work():
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"serving did not drain within {max_steps} steps")
            self.step()
            steps += 1
            if self.progress_counter != last_progress:
                last_progress = self.progress_counter
                stalled = 0
            else:
                stalled += 1
                if stall_steps is not None and stalled >= stall_steps \
                        and self.has_work():
                    raise EngineStalledError(stalled,
                                             self.stall_snapshot())
        return steps
