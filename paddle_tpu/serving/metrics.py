"""Serving telemetry facade, updated by the engine OFF the hot path.

Rebased onto ``paddle_tpu.obs``: every counter/gauge/histogram lives in
an :class:`~paddle_tpu.obs.MetricsRegistry` (Prometheus text exposition,
JSON snapshot, windowed rates) and every request carries a lifecycle
span trace in a ring-buffered :class:`~paddle_tpu.obs.Tracer` — while
``snapshot()`` keeps the exact dict shape earlier rounds shipped, plus
p50/p99 TTFT and TPOT from the new log-bucketed histograms.

Every update is a host-side op on values the engine already holds (no
extra device syncs: the engine's single per-step token readback feeds
everything — pinned by tests/test_observability.py).  Each step is a
live ``serving.step`` span with one ``step.<phase>`` child open at a
time (:meth:`ServingMetrics.begin_step` / ``phase`` / ``end_step``).
With ``record_events=True`` the tracer also writes every live span as a
``jax.profiler.TraceAnnotation`` — the same names on the device trace's
clock — and its lanes merge into ``profiler.export_chrome_tracing``
output.

CLOCK BASE: all timestamps entering this class MUST be
``time.perf_counter()`` readings — ``Scheduler.submit`` stamps
``Request.arrival_time`` from that clock and :meth:`on_first_token`
rejects arrivals from any other base (a ``time.time()`` arrival used to
silently corrupt the TTFT mean; now it raises).

The metric glossary lives in docs/observability.md.
"""

from __future__ import annotations

import math
import time
from typing import Dict, Optional, Sequence, Tuple

from ..obs import Histogram, MetricsRegistry, Tracer

__all__ = ["ServingMetrics", "StepSpans", "STEP_PHASES", "STEP_COUNTS"]

# the children of a ``serving.step`` span, in the order a step runs them
# (docs/observability.md "Step timeline"); each is ``step.<name>`` on
# the engine lane and a ``serving.phase.<name>_s`` histogram
STEP_PHASES = ("admission", "prefill", "first_token_readback", "draft",
               "decode_dispatch", "readback", "harvest", "bookkeeping")
# host ints the engine already holds, set on the ``serving.step`` span
STEP_COUNTS = ("admitted", "prefill_tokens", "prefills_completed",
               "active_slots", "sampling_slots", "live_kv_rows",
               "loop_passes", "state_slots", "new_tokens", "queue_depth",
               # a routing model's decode program, riding the step's one
               # token readback (0 for every other model): experts that
               # got at least one LIVE row, summed over the expert
               # layers, and the fullest expert's rows in any layer
               "experts_touched", "expert_rows_max",
               # one decode program in flight (docs/serving.md "One
               # program ahead"): 1 where this step's decode program was
               # dispatched before the previous one's tokens were read;
               # tokens this step read and dropped because their request
               # had ended before the read (a program ran its row once
               # more than the request needed)
               "decode_ahead", "overrun_tokens")

# admission-projection clamps: a degenerate measurement window (one
# finish inside a denormal-small busy window, or a finish against an
# hours-long idle-heavy window) must yield a FINITE, bounded hint — a
# retry_after_s of inf/nan/1e6 seconds is not a hint, it is a bug
# surfaced to every rejected client.  Projections cap higher than hints:
# a projection only needs to stay comparable against deadlines, while a
# hint is an actual "come back in N seconds" told to a caller.
MAX_RETRY_AFTER_S = 600.0
MAX_PROJECTED_TTFT_S = 3600.0


class StepSpans:
    """One engine step's live spans: the ``serving.step`` span and the
    ONE ``step.<phase>`` child open inside it.  Phases follow one
    another and one clock reading closes a phase and opens the next, so
    the children tile the step exactly.  ``counts`` lands on the step
    span as attrs when it closes; ``phases`` is the ``(name, start,
    end)`` list ``record_step`` feeds the phase histograms from (also
    where the tracer is disabled and records no span)."""

    __slots__ = ("index", "counts", "phases", "_root", "_open",
                 "_open_name", "_open_start")

    def __init__(self, index: int):
        self.index = index
        self.counts = dict.fromkeys(STEP_COUNTS, 0)
        self.phases = []
        self._root = self._open = self._open_name = None
        self._open_start = 0.0


class ServingMetrics:
    def __init__(self, record_events: bool = False,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        # record_events=True writes every live span as a profiler
        # TraceAnnotation too AND merges the tracer's lanes into chrome
        # exports
        self.record_events = record_events
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        if record_events and self.tracer.annotate is None:
            import jax
            self.tracer.annotate = jax.profiler.TraceAnnotation
        self._step: Optional[StepSpans] = None
        # (program, table of parts) per compile, from a traced engine
        self._program_parts: list = []
        # disjoint lane block per engine: the step timeline sits on
        # engine_lane, request r on engine_lane + 1 + r — two engines
        # sharing one tracer never collide on a lane
        self.engine_lane = self.tracer.claim_lane_block()
        self.tracer.set_lane_name(self.engine_lane, "serving.engine",
                                  pin=True)
        if record_events:
            self.tracer.install_profiler_source()
        self._bind()

    def close(self) -> None:
        """Detach from the profiler's chrome-export source list (the one
        global this object registers into).  Long-lived processes that
        churn ``record_events=True`` engines MUST close them, or every
        later export merges the dead engines' lanes too.  Only balances
        what __init__ installed — a record_events=False engine's close
        must not decrement a shared tracer's refcount for its peers."""
        if self.record_events:
            self.record_events = False      # idempotent: one remove
            self.tracer.remove_profiler_source()

    def request_lane(self, request_id: int) -> int:
        """Tracer lane for one request, folded into this engine's lane
        block (ids are unbounded; lanes wrap inside the block so they
        can never walk into a neighbour engine's reservation — the span
        ring is far smaller than the block, so a wrapped lane's previous
        tenant has long been evicted)."""
        return self.engine_lane + 1 + request_id % (Tracer.LANE_BLOCK - 1)

    def _bind(self) -> None:
        """Get-or-create this engine's instruments in the registry.
        Binding never zeroes anything — constructing a second engine
        onto a SHARED registry/tracer must not wipe the first one's
        accumulated data (the instruments are then shared and both
        engines aggregate into them).  Everything bound here lands in
        ``self._own`` — the single list reset() iterates, so a new
        instrument can never be forgotten by reset."""
        reg = self.registry
        self._own = []
        own = self._own.append

        def c(*a, **kw):
            inst = reg.counter(*a, **kw)
            own(inst)
            return inst

        def h(*a, **kw):
            inst = reg.histogram(*a, **kw)
            own(inst)
            return inst

        def g(*a, **kw):
            inst = reg.gauge(*a, **kw)
            own(inst)
            return inst

        self._c_submitted = c("serving.requests_submitted",
                              "requests accepted by submit()")
        self._c_finished = c("serving.requests_finished",
                             "requests that reached eos/length")
        self._c_tokens = c("serving.tokens_generated",
                           "output tokens harvested")
        self._c_prefills = c("serving.prefills",
                             "completed request prefills")
        self._c_prefill_tokens = c("serving.prefill_tokens",
                                   "prompt tokens actually prefilled "
                                   "(uncached suffixes)")
        self._c_prefill_chunks = c("serving.prefill_chunks",
                                   "prefill chunk programs dispatched")
        self._c_prefill_chunk_tokens = c("serving.prefill_chunk_tokens",
                                         "real tokens covered by chunks")
        self._c_prefix_hits = c("serving.prefix_hits",
                                "admissions with a radix-cache match")
        self._c_prefix_hit_tokens = c("serving.prefix_hit_tokens",
                                      "prompt tokens served from cache")
        self._c_steps = c("serving.steps", "engine step() iterations")
        self._c_compiles = c("serving.compiles",
                             "program (re)traces seen by trace counters")
        self._h_ttft = h("serving.ttft_s",
                         "submit -> first generated token", unit="s")
        self._h_tpot = h("serving.tpot_s",
                         "per-output-token latency after the first",
                         unit="s")
        self._h_queue_wait = h("serving.queue_wait_s",
                               "submit -> admission", unit="s")
        self._h_gather = h("serving.gather_s",
                           "prefix block gather / staging init", unit="s")
        self._g_queue_depth = g("serving.queue_depth",
                                "waiting requests at the last step")
        # robustness surface (docs/serving.md "Fault tolerance"): the
        # terminal-status counters partition every submitted request —
        # finished + cancelled + deadline_exceeded + failed (+ rejected,
        # which never enters the queue) == submitted, once drained
        self._c_cancelled = c("serving.requests_cancelled",
                              "requests unwound by cancel()")
        self._c_deadline = c("serving.requests_deadline_exceeded",
                             "requests terminated by a blown deadline")
        self._c_failed = c("serving.requests_failed",
                           "requests terminally failed by a fault")
        self._c_rejected = c("serving.requests_rejected",
                             "submissions refused (backpressure/SLO/"
                             "circuit)")
        self._c_faults = c("serving.faults",
                           "faults observed by the watchdog (injected "
                           "or real)")
        self._c_retries = c("serving.step_retries",
                            "watchdog step retries (backoff sleeps)")
        self._c_quarantines = c("serving.quarantines",
                                "quarantine rebuilds of the device plane")
        self._g_health = g("serving.health_state",
                           "0 healthy / 1 degraded / 2 quarantined / "
                           "3 circuit_open")
        self._g_degradation = g("serving.degradation_level",
                                "optional subsystems disabled by the "
                                "degradation ladder")
        # tensor-parallel serving surface (docs/serving.md
        # "Tensor-parallel serving"): the mesh degree this engine
        # shards over.  On a TP mesh every decode step's
        # serving.phase.decode_dispatch_s + readback_s carries its
        # fused entry/exit collectives — compare against a tp=1 engine's.
        # The tp gauge binds OUTSIDE self._own: the degree is an
        # engine-lifetime constant published once at construction, and
        # the warmup->reset()->measure flow must not zero it into a
        # lying 0 on every later scrape (health_state survives reset by
        # being re-published each step; nothing re-publishes this)
        self._g_tp = reg.gauge("serving.tp_degree",
                               "tensor-parallel mesh degree "
                               "(1 = single chip)")
        # KV planes a cached position spans (kv_pool.cache_geometry): a
        # plane per layer, per pass per layer for a looped model; x
        # 2 * kv_heads * head_dim * itemsize is a cached row's bytes.
        # An engine-lifetime constant like the tp degree, bound outside
        # self._own for the same reason
        self._g_kv_planes = reg.gauge("serving.kv.planes",
                                      "KV planes one cached position "
                                      "spans (layers x passes)")
        self._g_state_bytes = reg.gauge(
            "serving.state.bytes_per_slot",
            "bytes of recurrent state a slot holds beside its KV rows "
            "(0 for a model without one)")
        self._g_row_bytes = reg.gauge(
            "serving.cache.row_bytes",
            "bytes ONE cached position holds over every layer: K and V "
            "rows, or the one latent row of a model that declares one "
            "row kind (kv_pool.cache_row)")
        self._g_moe_experts = reg.gauge(
            "serving.moe.experts",
            "routed experts the model holds over its expert layers "
            "(0 for a model without expert layers)")
        # zero-cold-start surface (docs/serving.md "Zero cold start"):
        # warm-load accounting for the AOT program store.  The event
        # counters window-reset with the rest; the two gauges are
        # engine-lifetime facts (how long THIS engine's warm load took,
        # how long the store's build took) and bind outside self._own
        # for the same reason as serving.tp_degree — nothing would ever
        # re-publish them after a bench warmup reset
        self._c_aot_loads = c("aot.loads",
                              "programs warm-loaded from the AOT store "
                              "instead of traced")
        self._c_aot_misses = c("aot.misses",
                               "AOT lookups with no usable artifact "
                               "(fingerprint skew / leg not in store)")
        self._c_aot_fallbacks = c("aot.fallbacks",
                                  "AOT load attempts that failed "
                                  "(corrupt artifact, version skew, "
                                  "injected fault) and degraded to "
                                  "tracing")
        self._g_aot_load_s = reg.gauge("aot.load_s",
                                       "wall seconds the engine's last "
                                       "warm load spent")
        self._g_aot_build_s = reg.gauge("aot.build_s",
                                        "wall seconds the attached "
                                        "store's builder spent "
                                        "exporting (from the store "
                                        "index)")
        # speculative-decoding surface (docs/serving.md "Speculative
        # decoding"): draft tokens proposed vs draft tokens the verify
        # program accepted.  accepted/draft is the acceptance rate — the
        # single number that predicts the speedup (each accepted token
        # is a decode step the engine did not pay for)
        self._c_spec_draft = c("spec.draft_tokens",
                               "draft tokens proposed by the n-gram "
                               "tables (verify-window fill)")
        self._c_spec_accept = c("spec.accepted_tokens",
                                "draft tokens the verify program "
                                "accepted (free decode steps)")
        self._c_overrun = c("serving.overrun_tokens",
                            "tokens read and dropped because their "
                            "request had ended before the read (the "
                            "next program was already in flight)")
        self._last_health_state: Optional[str] = None
        self._phase_h: Dict[str, Histogram] = {}
        self._zero_local()

    def _zero_local(self) -> None:
        # per-ENGINE tallies feeding the derived rates: with a shared
        # registry the counters aggregate the whole fleet, so dividing
        # them by this engine's busy time would inflate every rate —
        # rates and ratios always describe THIS engine
        self._busy_s = 0.0
        self._queue_depth_sum = 0
        self._occupancy_sum = 0.0
        self._tokens_local = 0
        self._steps_local = 0
        self._finished_local = 0
        self._spec_draft_local = 0
        self._spec_accept_local = 0

    def reset(self) -> None:
        """Zero THIS engine's instruments and drop the tracer's recorded
        spans/events (fresh measurement window — bench warmup vs
        measure).  Only the serving instruments bound here reset; other
        producers' metrics in a shared registry (a trainer's ``train.*``
        histograms) are untouched.  A shared TRACER's ring is one buffer,
        so its clear does drop every producer's spans — give each engine
        its own tracer when traces must survive a neighbour's reset."""
        for inst in (*self._own, *self._phase_h.values()):
            inst.reset()
        self.tracer.clear()
        # a program is compiled once and its table recorded then: a
        # window that starts after warm-up must still find it
        for program, parts in self._program_parts:
            self._add_program_parts(program, parts)
        self._zero_local()

    # ------------------------------------------------------------ events
    def on_submit(self, n: int = 1) -> None:
        self._c_submitted.inc(n)

    def on_prefill(self, prompt_len: int) -> None:
        """One request's prefill completed; ``prompt_len`` counts only
        the tokens the model actually ran (the uncached suffix) — the
        FLOPs-saved story is ``prefix_hit_tokens`` vs this."""
        self._c_prefills.inc()
        self._c_prefill_tokens.inc(prompt_len)

    def on_prefill_chunk(self, tokens: int) -> None:
        """One chunk program dispatched, covering ``tokens`` real (non-
        padding) prompt tokens (its dispatch interval is the request
        lane's ``prefill_chunk`` span)."""
        self._c_prefill_chunks.inc()
        self._c_prefill_chunk_tokens.inc(tokens)

    def on_prefix_hit(self, tokens: int) -> None:
        """Admission matched ``tokens`` prompt tokens in the radix cache
        (their KV was copied, not recomputed)."""
        self._c_prefix_hits.inc()
        self._c_prefix_hit_tokens.inc(tokens)

    def on_queue_wait(self, seconds: float) -> None:
        self._h_queue_wait.observe(seconds)

    def on_gather(self, seconds: float) -> None:
        self._h_gather.observe(seconds)

    def on_decode_block(self, active: bool, reason: Optional[str],
                        step: int = 0, tp: int = 1,
                        attention_route: str = "",
                        attention_reason: Optional[str] = None,
                        kv_append: str = "",
                        kv_append_reason: Optional[str] = None,
                        scan_route: str = "",
                        scan_reason: Optional[str] = None,
                        expert_route: str = "",
                        expert_reason: Optional[str] = None,
                        prefill_attention_route: str = "",
                        prefill_attention_reason: Optional[str] = None,
                        overlap: str = "",
                        overlap_reason: Optional[str] = None) -> None:
        """The engine resolved its decode path (emitted once, when the
        single decode program is built): ``active`` says whether the
        fused decode-block kernels compiled in, ``reason`` carries the
        fallback cause when the flag asked for fusion but routing or
        legality refused (None when fused engaged or the flag was off),
        and ``tp`` records the mesh degree — ``active`` at ``tp > 1``
        means the SHARDED block (kernels/decode_block_tp.py), so traces
        from a shared registry separate the two fused variants.
        ``attention_route`` is how that program's attention reaches the
        KV slabs (``slab_in_place`` / ``head_major_copy`` /
        ``xla_dense``: kernels/decode_attention.py) and
        ``attention_reason`` why it is not ``slab_in_place``;
        ``kv_append`` is who writes a step's fresh rows into them
        (``in_kernel``: that attention kernel, by DMA; ``xla_scatter``:
        ``kv_cache.append_kv`` ahead of it) and ``kv_append_reason`` why
        it is not ``in_kernel``; ``scan_route`` names the form that
        runs a model's recurrence over positions in the prefill and
        decode programs (``prefill=<form>,decode=<form>``,
        kernels/selective_scan.py; empty for a model without one) and
        ``scan_reason`` why the prefill form is not the kernel;
        ``expert_route`` the form of a routing model's grouped matmul
        over its experts, likewise (``gmm`` / ``ragged_dot``,
        distributed/moe_dropless.py; empty for a model without expert
        layers) and ``expert_reason`` why the decode program's is not
        the kernel; ``prefill_attention_route`` how a model that
        declares one attends over a request's staging in the prefill
        programs (``latent_chunk``: kernels/latent_attention.py's chunk
        kernel, which reads the rows up to each query tile's causal
        edge; ``xla_dense``: every row; empty for every other model)
        and ``prefill_attention_reason`` why not the kernel;
        ``overlap`` whether the engine dispatches a step's
        decode program before it reads the previous one's tokens
        (``one_ahead``) or reads each program before the next
        (``none``), and ``overlap_reason`` why not ``one_ahead``
        (``speculation``: the next dispatch needs this step's tokens on
        the host).  Lands
        as a ``decode_block`` discrete event on the engine lane
        (glossary: docs/observability.md)."""
        self.tracer.event("decode_block", lane=self.engine_lane,
                          active=active,
                          reason=reason if reason is not None else "",
                          step=step, tp=tp,
                          attention_route=attention_route,
                          attention_reason=attention_reason or "",
                          kv_append=kv_append,
                          kv_append_reason=kv_append_reason or "",
                          scan_route=scan_route,
                          scan_reason=scan_reason or "",
                          expert_route=expert_route,
                          expert_reason=expert_reason or "",
                          prefill_attention_route=prefill_attention_route,
                          prefill_attention_reason=(
                              prefill_attention_reason or ""),
                          overlap=overlap,
                          overlap_reason=overlap_reason or "")

    def on_aot_load(self, programs: int, seconds: float,
                    build_s: Optional[float] = None) -> None:
        """The engine finished a warm load: ``programs`` artifacts
        installed from the AOT store in ``seconds`` of wall time
        (``build_s``: the store's recorded builder time, republished as
        the ``aot.build_s`` gauge so one scrape shows both halves of
        the build-once/load-many trade).  Lands as an ``aot_load``
        discrete event on the engine lane."""
        self._c_aot_loads.inc(programs)
        self._g_aot_load_s.set(seconds)
        if build_s is not None:
            self._g_aot_build_s.set(build_s)
        self.tracer.event("aot_load", lane=self.engine_lane,
                          programs=programs, seconds=round(seconds, 6))

    def on_aot_miss(self, program: str, reason: str) -> None:
        """An AOT lookup found no usable artifact (store fingerprint
        skew, or ``program``'s leg absent) — the engine traces instead.
        A degradation event (``aot_miss``), never an error."""
        self._c_aot_misses.inc()
        self.tracer.event("aot_miss", lane=self.engine_lane,
                          program=program, reason=reason)

    def on_aot_fallback(self, program: str, reason: str) -> None:
        """An AOT load ATTEMPT failed (corrupt artifact, deserialize
        skew, injected ``aot_load`` fault) and ``program`` degraded to
        trace-on-demand.  Lands as an ``aot_fallback`` event."""
        self._c_aot_fallbacks.inc()
        self.tracer.event("aot_fallback", lane=self.engine_lane,
                          program=program, reason=reason)

    def set_tp_degree(self, tp: int) -> None:
        self._g_tp.set(tp)

    def set_kv_planes(self, planes: int) -> None:
        self._g_kv_planes.set(planes)

    def set_state_bytes(self, nbytes: int) -> None:
        self._g_state_bytes.set(nbytes)

    def set_cache_row_bytes(self, nbytes: int) -> None:
        self._g_row_bytes.set(nbytes)

    def set_moe_experts(self, experts: int) -> None:
        self._g_moe_experts.set(experts)

    def on_program_parts(self, program: str, parts: dict) -> None:
        """One compile of ``program`` (``jit_decode``) and its table
        ``{operation: (part, phase)}`` (``obs.parts.program_parts``):
        kept for the engine's life and recorded as ONE zero-length
        ``program.parts`` span on the engine lane, again after every
        :meth:`reset`.  What sums a device trace by part reads it
        (``benchmarks/lib/parts.py``)."""
        self._program_parts.append((program, parts))
        self._add_program_parts(program, parts)

    def _add_program_parts(self, program: str, parts: dict) -> None:
        now = time.perf_counter()
        self.tracer.add_span("program.parts", self.engine_lane, now, now,
                             program=program, parts=parts)

    def on_compile(self, program: str, n: int = 1) -> None:
        self._c_compiles.inc(n)

    def on_first_token(self, arrival_t: float,
                       now: Optional[float] = None) -> None:
        """Record one TTFT sample.  ``arrival_t`` MUST be a
        ``time.perf_counter()`` reading (``Request.arrival_time`` as
        ``Scheduler.submit`` stamps it).  A ``time.time()`` arrival sits
        decades ahead of the perf_counter epoch, so the mismatch is
        detected and raised instead of silently feeding a garbage mean
        (the pre-obs bug this signature change fixes)."""
        if now is None:
            now = time.perf_counter()
        ttft = now - arrival_t
        if ttft < 0:
            raise ValueError(
                f"on_first_token: arrival_t {arrival_t!r} is ahead of "
                f"perf_counter now {now!r} — arrival timestamps must be "
                f"time.perf_counter() readings, not time.time() (mixed "
                f"clock bases corrupt TTFT)")
        self._h_ttft.observe(ttft)

    def on_output_token(self, seconds: float) -> None:
        """One decode token's latency since the request's previous
        token (TPOT — the steady-state per-token serving cost)."""
        self._h_tpot.observe(seconds)

    def on_finish(self, n: int = 1) -> None:
        self._c_finished.inc(n)
        self._finished_local += n

    # ----------------------------------------------- robustness events
    def on_terminal(self, status: str, reason: str, request_id: int,
                    now: Optional[float] = None) -> None:
        """One request reached an ABNORMAL terminal status (normal
        completion goes through :meth:`on_finish`): count it and drop a
        discrete event on the request's lane so the trace shows why the
        lifecycle ended."""
        counter = {"cancelled": self._c_cancelled,
                   "deadline_exceeded": self._c_deadline,
                   "failed": self._c_failed,
                   "rejected": self._c_rejected}.get(status)
        if counter is None:
            raise ValueError(f"unknown terminal status {status!r}")
        counter.inc()
        self.tracer.event("request_" + status,
                          lane=self.request_lane(request_id),
                          t=now, request=request_id, reason=reason)

    def on_fault(self, site: str, error: str, step: int = 0) -> None:
        """The watchdog observed one fault (injected or real) attributed
        to ``site`` (an injection-point or subsystem name)."""
        self._c_faults.inc()
        self.tracer.event("fault", lane=self.engine_lane, site=site,
                          error=error[:200], step=step)

    def on_retry(self, attempt: int, backoff_s: float,
                 step: int = 0) -> None:
        self._c_retries.inc()
        self.tracer.event("step_retry", lane=self.engine_lane,
                          attempt=attempt, backoff_s=round(backoff_s, 4),
                          step=step)

    def on_spec(self, drafted: int, accepted: int) -> None:
        """One speculative step's draft/accept tally (the engine calls
        this after the harvest of a verify window, never between device
        dispatches)."""
        self._c_spec_draft.inc(drafted)
        self._c_spec_accept.inc(accepted)
        self._spec_draft_local += drafted
        self._spec_accept_local += accepted

    def on_overrun(self, tokens: int) -> None:
        """A harvest dropped ``tokens`` rows whose request had ended
        before their program was read (an ``eos`` the host learnt one
        program late, a cancel or a deadline in between): work the chip
        did, never a result.  Also ``overrun_tokens`` on the step's
        span."""
        self._c_overrun.inc(tokens)
        self._step.counts["overrun_tokens"] += tokens

    def on_spec_disable(self, reason: str) -> None:
        """The degradation ladder (or an unsatisfiable constraint)
        turned speculation off — drop the discrete event so the trace
        shows when the engine fell back to one token per step."""
        self.tracer.event("spec_disable", lane=self.engine_lane,
                          reason=reason[:200])

    def on_degrade(self, subsystem: str, level: int, reason: str) -> None:
        """The degradation ladder disabled an optional subsystem; the
        gauge tracks the ladder level, the event carries which and why."""
        self._g_degradation.set(level)
        self.tracer.event("degrade", lane=self.engine_lane,
                          subsystem=subsystem, level=level,
                          reason=reason[:200])

    def on_health_state(self, state: str, code: int,
                        step: int = 0) -> None:
        """Track the health state machine: the gauge always reflects the
        latest state; the discrete event fires only on TRANSITIONS so
        a million healthy steps cost one event, not a million."""
        self._g_health.set(code)
        if state != self._last_health_state:
            self.tracer.event("health_state", lane=self.engine_lane,
                              state=state, step=step)
            self._last_health_state = state

    def on_quarantine(self, phase: str, reason: str, step: int = 0,
                      seconds: Optional[float] = None) -> None:
        """``phase`` is "enter" or "leave"; one quarantine rebuild
        counts once (on enter)."""
        if phase == "enter":
            self._c_quarantines.inc()
        attrs = {"reason": reason[:200], "step": step}
        if seconds is not None:
            attrs["seconds"] = round(seconds, 4)
        self.tracer.event(f"quarantine_{phase}", lane=self.engine_lane,
                          **attrs)

    # -------------------------------------------- admission projections
    @property
    def completion_rate(self) -> Optional[float]:
        """Requests completed per second of engine busy time — the live
        throughput estimate backpressure hints derive from (None until
        at least one request finished in this window).  Degenerate
        windows — a finish counted against a denormal-small or infinite
        busy time, where the division returns inf or 0.0 — also report
        None: the hint/projection corners below must never divide by a
        zero rate (a 0.0 rate used to raise ZeroDivisionError out of
        ``retry_after_hint``, and an inf rate projected a 0.0 TTFT that
        admitted hopeless requests)."""
        if self._finished_local <= 0 or self._busy_s <= 0:
            return None
        rate = self._finished_local / self._busy_s
        if not math.isfinite(rate) or rate <= 0.0:
            return None
        return rate

    def retry_after_hint(self, excess: int = 1) -> Optional[float]:
        """Seconds until ~``excess`` queue positions should free, from
        the live completion rate.  None with no history — callers
        surface that as "no hint" rather than inventing a number.
        Always finite and clamped to :data:`MAX_RETRY_AFTER_S`: a
        near-zero rate (one finish against an idle-heavy window) must
        not tell a client to come back in 1e6 seconds."""
        rate = self.completion_rate
        if rate is None:
            return None
        return min(max(excess, 1) / rate, MAX_RETRY_AFTER_S)

    def projected_ttft_s(self, queue_depth: int) -> Optional[float]:
        """SLO-aware admission estimate: time for the current queue to
        drain ahead of a new arrival plus the live p50 TTFT.  A
        heuristic, deliberately simple — it only needs to be right
        enough to reject requests that are HOPELESSLY late, not to
        schedule precisely.  None with no history (cold engines admit;
        rejecting on zero data would deadlock the very first request);
        otherwise finite, clamped to :data:`MAX_PROJECTED_TTFT_S` so
        deadline comparisons never meet an inf/nan."""
        rate = self.completion_rate
        if rate is None:
            return None
        base = self._h_ttft.quantile(0.50) or 0.0
        return min(queue_depth / rate + base, MAX_PROJECTED_TTFT_S)

    # ---------------------------------------------------- the step's spans
    def begin_step(self, index: int, phase: str) -> StepSpans:
        """Open step ``index``'s ``serving.step`` span on the engine
        lane and its first phase.  ``begin_step``/``end_step`` is a
        registered graftlint ``ResourcePair``: the engine ends it in a
        ``finally``, which closes the open phase first, so a step that
        raises mid-phase leaves no span (or trace annotation) open and
        the nesting holds."""
        st = StepSpans(index)
        now = time.perf_counter()
        st._root = self.tracer.begin_span(
            "serving.step", lane=self.engine_lane, t=now, step=index)
        st._open = self.tracer.begin_span(
            "step." + phase, lane=self.engine_lane, t=now, step=index)
        st._open_name, st._open_start = phase, now
        self._step = st
        return st

    def phase(self, name: str) -> None:
        """The step in flight moves on to ``step.<name>``: the open
        phase closes and the next opens, where the work happens (one
        clock reading for both; a few of these run every step, so no
        helper calls)."""
        st = self._step
        tracer = self.tracer
        now = time.perf_counter()
        tracer.end_span(st._open, t=now)
        st.phases.append((st._open_name, st._open_start, now))
        st._open = tracer.begin_span(
            "step." + name, lane=self.engine_lane, t=now, step=st.index)
        st._open_name, st._open_start = name, now

    def step_count(self, key: str, n: int) -> None:
        """Add ``n`` to one of :data:`STEP_COUNTS` of the step in flight
        (a host int the caller already holds — never a device value)."""
        self._step.counts[key] += n

    def late_step_counts(self, st: StepSpans, **counts: int) -> None:
        """Counts of step ``st``'s decode program that arrive WITH its
        tokens (a routing model's ``experts_touched`` /
        ``expert_rows_max``).  One program ahead the tokens are read a
        step later, when ``st``'s span has closed: the counts still land
        on THAT span, so that every count on one ``serving.step`` span
        that describes a decode program describes the same program."""
        st.counts.update(counts)
        if st._root is not None:
            st._root.attrs.update(counts)

    def end_step(self, st: StepSpans) -> None:
        """Close the open phase, put the counts on the ``serving.step``
        span and close it."""
        now = time.perf_counter()
        self.tracer.end_span(st._open, t=now)
        st.phases.append((st._open_name, st._open_start, now))
        if st._root is not None:
            st._root.attrs.update(st.counts)
        self.tracer.end_span(st._root, t=now)
        self._step = None

    def record_step(self, active_slots: int, num_slots: int,
                    queue_depth: int, new_tokens: int,
                    step_seconds: float,
                    phases: Optional[Sequence[Tuple[str, float, float]]]
                    = None) -> None:
        """One engine step's accounting (called after the token harvest —
        never between device dispatches).  ``phases`` is the step's
        timeline breakdown as ``(name, start, end)`` perf_counter
        triples (:attr:`StepSpans.phases`: the ``step.<name>`` spans
        were written live); each lands in a ``serving.phase.<name>_s``
        histogram."""
        occupancy = active_slots / max(num_slots, 1)
        self._c_steps.inc()
        self._c_tokens.inc(new_tokens)
        self._busy_s += step_seconds
        self._queue_depth_sum += queue_depth
        self._occupancy_sum += occupancy
        self._tokens_local += new_tokens
        self._steps_local += 1
        self._g_queue_depth.set(queue_depth)
        if phases:
            for name, start, end in phases:
                hp = self._phase_h.get(name)
                if hp is None:
                    hp = self.registry.histogram(
                        f"serving.phase.{name}_s",
                        f"step phase: {name}", unit="s")
                    self._phase_h[name] = hp
                hp.observe(end - start)

    # --------------------------------------------------------- counters
    # lifetime counts read as plain ints (the pre-registry attribute API)
    @property
    def requests_submitted(self) -> int:
        return self._c_submitted.value

    @property
    def requests_finished(self) -> int:
        return self._c_finished.value

    @property
    def tokens_generated(self) -> int:
        return self._c_tokens.value

    @property
    def prefills(self) -> int:
        return self._c_prefills.value

    @property
    def prefill_tokens(self) -> int:
        return self._c_prefill_tokens.value

    @property
    def prefill_chunks(self) -> int:
        return self._c_prefill_chunks.value

    @property
    def prefill_chunk_tokens(self) -> int:
        return self._c_prefill_chunk_tokens.value

    @property
    def prefix_hits(self) -> int:
        return self._c_prefix_hits.value

    @property
    def prefix_hit_tokens(self) -> int:
        return self._c_prefix_hit_tokens.value

    @property
    def steps(self) -> int:
        return self._c_steps.value

    # ---------------------------------------------------------- derived
    @property
    def mean_ttft_ms(self) -> Optional[float]:
        m = self._h_ttft.mean
        return None if m is None else 1e3 * m

    def _q_ms(self, hist: Histogram, q: float) -> Optional[float]:
        v = hist.quantile(q)
        return None if v is None else 1e3 * v

    @property
    def ttft_p50_ms(self) -> Optional[float]:
        return self._q_ms(self._h_ttft, 0.50)

    @property
    def ttft_p99_ms(self) -> Optional[float]:
        return self._q_ms(self._h_ttft, 0.99)

    @property
    def tpot_p50_ms(self) -> Optional[float]:
        return self._q_ms(self._h_tpot, 0.50)

    @property
    def tpot_p99_ms(self) -> Optional[float]:
        return self._q_ms(self._h_tpot, 0.99)

    # rates/ratios divide per-engine tallies by per-engine denominators:
    # under a shared registry the counter properties above aggregate the
    # fleet, and mixing the two would inflate every derived value
    @property
    def tokens_per_sec(self) -> Optional[float]:
        if self._busy_s <= 0:
            return None
        return self._tokens_local / self._busy_s

    @property
    def batch_fill_ratio(self) -> Optional[float]:
        if self._steps_local == 0:
            return None
        return self._occupancy_sum / self._steps_local

    @property
    def mean_queue_depth(self) -> Optional[float]:
        if self._steps_local == 0:
            return None
        return self._queue_depth_sum / self._steps_local

    @property
    def spec_acceptance_rate(self) -> Optional[float]:
        """Accepted / drafted over THIS engine's window (None until the
        first speculative step) — the number that predicts the
        speculative speedup."""
        if self._spec_draft_local <= 0:
            return None
        return self._spec_accept_local / self._spec_draft_local

    # ---------------------------------------------------------- snapshot
    def snapshot(self) -> Dict[str, object]:
        """The engine-counter dict earlier rounds shipped, extended with
        the histogram quantiles (keys only ever ADD — consumers pin on
        key presence).  The full instrument dump (every histogram's
        count/sum/p50/p90/p99) is ``self.registry.snapshot()``."""
        r = lambda v, nd=4: None if v is None else round(v, nd)
        return {
            "requests_submitted": self.requests_submitted,
            "requests_finished": self.requests_finished,
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "prefill_tokens": self.prefill_tokens,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "steps": self.steps,
            "tokens_per_sec": r(self.tokens_per_sec, 1),
            "mean_ttft_ms": r(self.mean_ttft_ms, 2),
            "ttft_p50_ms": r(self.ttft_p50_ms, 2),
            "ttft_p99_ms": r(self.ttft_p99_ms, 2),
            "tpot_p50_ms": r(self.tpot_p50_ms, 3),
            "tpot_p99_ms": r(self.tpot_p99_ms, 3),
            "batch_fill_ratio": r(self.batch_fill_ratio),
            "mean_queue_depth": r(self.mean_queue_depth, 2),
            # robustness block (keys only ever ADD — see class docstring)
            "requests_cancelled": self._c_cancelled.value,
            "requests_deadline_exceeded": self._c_deadline.value,
            "requests_failed": self._c_failed.value,
            "requests_rejected": self._c_rejected.value,
            "faults": self._c_faults.value,
            "step_retries": self._c_retries.value,
            "quarantines": self._c_quarantines.value,
            "health_state": self._g_health.value,
            "degradation_level": self._g_degradation.value,
            # speculative decoding block (keys only ever ADD)
            "spec_draft_tokens": self._c_spec_draft.value,
            "spec_accepted_tokens": self._c_spec_accept.value,
            "spec_acceptance_rate": r(self.spec_acceptance_rate),
            # one decode program in flight (keys only ever ADD)
            "overrun_tokens": self._c_overrun.value,
        }
