"""Open-loop serving: requests are submitted when they are DUE, whatever
the engine is doing, through ``ServingEngine.submit/step`` on one thread.

Each loop turn submits every request now due, then calls ``step()``.
Every token is timestamped by the benchmark itself through the
``stream`` callback, and a request's first-token time counts from its
due time, so a stall charges the requests queued behind it.  After the
window the generator stops and the engine drains for a bounded time;
what has not finished then has failed.
"""

import functools
import gc
import time

import numpy as np

from benchmarks.lib import (flops_bytes, reference, stats, traffic,
                            xplane)
from benchmarks.lib.build import build_model
from benchmarks.lib.compile_clock import CompileClock


class _Live:
    """One request as the benchmark sees it."""
    __slots__ = ("arrival", "submit_t", "times", "request_id")

    def __init__(self, arrival):
        self.arrival = arrival
        self.submit_t = None
        self.times = []         # perf_counter of every emitted token
        self.request_id = None


def _warm_lengths(core, lo: int, hi: int) -> list:
    """One prompt length per distinct set of prefill program widths the
    lengths ``lo..hi`` can reach, by the engine's own chunk plan."""
    reps = {}
    for n in range(lo, hi + 1):
        widths = tuple(w for _, w, _ in core.scheduler.chunk_plan(
            0, n, core.prefill_chunk))
        reps[widths] = n
    return sorted(reps.values())


def _drain(eng, budget_s: float) -> bool:
    end = time.perf_counter() + budget_s
    while eng.step():
        if time.perf_counter() > end:
            return False
    return True


def warm_up(eng, lens, vocab: int, rs, log) -> dict:
    """Compile every program the window can need: one request per
    reachable prefill width, a repeated prompt (prefix hit: the block
    gather), and a burst of every size up to the engine's cap on prefills
    per step (the engine reads the first tokens of all prefills that
    complete in one step in one concatenated readback, whose shape is
    the burst's size)."""
    core = eng.core
    lo, hi = lens["min"], lens["max"]
    prompts = [rs.integers(0, vocab, n, dtype=np.int32)
               for n in _warm_lengths(core, lo, hi)]
    for p in prompts + [prompts[-1]]:
        eng.submit(p, max_new_tokens=2)
        if not _drain(eng, 600.0):
            raise RuntimeError("warm-up: the engine did not drain")
    short = prompts[0]
    most = core.scheduler.max_prefills_per_step or core.num_slots
    for burst in range(min(most, core.num_slots), 0, -1):
        for _ in range(burst):
            eng.submit(rs.integers(0, vocab, len(short), dtype=np.int32),
                       max_new_tokens=2)
        if not _drain(eng, 600.0):
            raise RuntimeError("warm-up: the engine did not drain")
    counts = _programs(core)
    log(f"warm-up: prompt lengths {[len(p) for p in prompts]} "
        f"programs {counts}")
    return counts


def build(ctx):
    """Model, engine and warm-up: everything before the window.  Returns
    ``(model, model_config, engine, programs_after_warm_up)``."""
    from paddle_tpu.obs import Tracer
    from paddle_tpu.serving import ServingEngine

    cfg, mix, log = ctx.config, ctx.traffic, ctx.log
    model, mcfg = build_model(ctx.builder, cfg, ctx.seed)
    engine_kw = dict(cfg.get("engine", {}))
    tracer = None
    if ctx.trace:
        # the default ring (4096 spans) holds about two seconds of steps
        tracer = Tracer(max_spans=1 << 21, max_events=1 << 16)
    eng = ServingEngine(model, record_events=ctx.trace, tracer=tracer,
                        **engine_kw)
    log(f"engine: decode_path={eng.decode_path} slots={eng.core.num_slots} "
        f"max_seq={eng.core.pool.max_seq} kwargs={engine_kw}")
    warm_rs = np.random.default_rng(np.random.SeedSequence([ctx.seed, 3]))
    programs_warm = warm_up(eng, mix["prompt_len"], mcfg.vocab_size,
                            warm_rs, log)
    return model, mcfg, eng, programs_warm


def _programs(core) -> dict:
    return {**core.trace_counts, **(core.block_pool.trace_counts
                                    if core.block_pool is not None else {})}


def window(ctx, eng, schedule, seconds: float) -> dict:
    """The measured window over ``schedule`` and the bounded drain after
    it.  Returns the raw readings; nothing is reduced here."""
    core, metrics, mix = eng.core, eng.metrics, ctx.traffic
    live = [_Live(a) for a in schedule]

    # what the decode program of a step must read, counted from the
    # benchmark's own token callbacks: slots past their first token, and
    # the cached rows they hold
    state = {"active": 0, "rows": 0, "released": []}

    def on_token(r: _Live, req, tok):
        r.times.append(time.perf_counter())
        n = len(r.times)
        if n == 1:
            state["active"] += 1
            state["rows"] += len(r.arrival.prompt)
        else:
            state["rows"] += 1
        if n >= r.arrival.max_new_tokens:
            state["released"].append(len(r.arrival.prompt) + n - 1)

    steps = []      # (t_begin, t_end, active, rows, prefilled tokens)
    tracing = None
    if ctx.trace:
        trace_from = seconds * mix["trace_start_share"]
        trace_to = min(trace_from + mix["trace_seconds"], seconds)
        tracing = xplane.TraceSlice(ctx.trace_dir)

    metrics.reset()
    gc.collect()
    n, i, in_flight = len(live), 0, 0
    queue = []      # (seconds into the window, queue depth) per turn
    with CompileClock() as clock:
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            queue.append((now, core.scheduler.queue_depth))
            if tracing is not None:
                if tracing.clock[0] is None and now >= trace_from:
                    tracing.start()
                elif tracing.running and now >= trace_to:
                    tracing.stop()
            while i < n and live[i].arrival.due_s <= now:
                r = live[i]
                r.submit_t = time.perf_counter()
                r.request_id = eng.submit(
                    r.arrival.prompt,
                    max_new_tokens=r.arrival.max_new_tokens,
                    stream=functools.partial(on_token, r))
                i += 1
                in_flight = 1
            if in_flight:
                before = metrics.prefill_tokens + metrics.prefill_chunk_tokens
                tb = time.perf_counter()
                in_flight = eng.step()
                te = time.perf_counter()
                steps.append((tb, te, state["active"], state["rows"],
                              metrics.prefill_tokens
                              + metrics.prefill_chunk_tokens - before))
                for rows in state["released"]:
                    state["active"] -= 1
                    state["rows"] -= rows
                state["released"].clear()
            else:
                gap = live[i].arrival.due_s - now if i < n else seconds - now
                time.sleep(min(max(gap, 0.0), 0.0005))
        t_end = time.perf_counter()
        if tracing is not None and tracing.running:
            tracing.stop()
        occupancy = metrics.batch_fill_ratio
        window_programs = clock.snapshot()
        memory_peak = ctx.memory_peak()
        drained = _drain(eng, mix["drain_seconds"]) \
            if in_flight else True
        t_drained = time.perf_counter()
        clock_all = clock.snapshot()

    def depth(lo, hi):
        """Mean queue depth between two shares of the window."""
        band = [d for t, d in queue if lo * seconds <= t <= hi * seconds]
        return float(np.mean(band)) if band else 0.0

    queue_mid, queue_end = depth(0.45, 0.55), depth(0.9, 1.0)
    live = live[:i]             # every request due inside the window
    outs = [eng.result(r.request_id) for r in live]
    finished = [o.finished and o.status == "finished"
                and len(r.times) == r.arrival.max_new_tokens
                for o, r in zip(outs, live)]
    worst = t_drained - t0
    return {
        "t0": t0, "t_end": t_end, "t_drained": t_drained, "live": live,
        "outs": outs, "finished": finished, "drained": drained,
        "steps": steps, "occupancy": occupancy, "queue_mid": queue_mid,
        "queue_end": queue_end, "window_programs": window_programs,
        "clock_all": clock_all, "memory_peak": memory_peak,
        "window_tokens": sum(1 for r in live for t in r.times if t < t_end),
        "ttft": [(r.times[0] - t0 - r.arrival.due_s) if ok
                 else worst - r.arrival.due_s
                 for r, ok in zip(live, finished)],
        "gaps": [b - a for r in live for a, b in zip(r.times, r.times[1:])],
        "late": [r.submit_t - t0 - r.arrival.due_s for r in live],
        "trace_clock": tuple(tracing.clock) if tracing else None,
    }


def run(ctx) -> dict:
    cfg, mix, log, builder = ctx.config, ctx.traffic, ctx.log, ctx.builder
    model, mcfg, eng, programs_warm = build(ctx)
    facts = builder.facts(cfg)
    schedule = traffic.open_loop_schedule(mix, ctx.seed, ctx.seconds,
                                          mcfg.vocab_size)
    setup_s = time.perf_counter() - ctx.t_start
    w = window(ctx, eng, schedule, ctx.seconds)
    outs, finished, steps = w["outs"], w["finished"], w["steps"]
    ttft, gaps, late = w["ttft"], w["gaps"], w["late"]
    due_n = len(outs)
    failed = due_n - sum(finished)
    programs_end = _programs(eng.core)
    spans = None
    if ctx.trace:
        spans = [(s.name, s.start, s.end, dict(s.attrs or {}))
                 for s in eng.tracer.spans()]
    num_slots = eng.core.num_slots

    # ----------------------------------------------------------- correct
    # a seeded sample of finished requests through the plain reference:
    # every emitted token must be a near-argmax of the reference logits
    # at its position.  The engine goes first: the float32 working copy
    # needs its memory.
    sample_rs = np.random.default_rng(np.random.SeedSequence([ctx.seed, 4]))
    done = [k for k, ok in enumerate(finished) if ok]
    picks = [done[k] for k in sample_rs.permutation(len(done))
             [:mix["reference_samples"]]]
    eng.close()
    del eng
    gc.collect()
    gap_worst = None
    if picks:
        from paddle_tpu.nn.functional_call import state as model_state
        params, _ = model_state(model)
        seqs = [outs[k].sequence[:-1] for k in picks]
        refs = reference.reference_logits(builder, cfg, params, seqs)
        gap_worst = max(
            reference.argmax_gap(ref[len(outs[k].prompt) - 1:],
                                 outs[k].tokens)
            for ref, k in zip(refs, picks))
    tol = reference.logit_tol(mcfg.dtype)
    checks = {
        "all_due_finished": failed == 0 and w["drained"],
        "no_program_in_window": w["clock_all"]["programs"] == 0
        and programs_end == programs_warm,
        "tokens_near_reference_argmax": gap_worst is not None
        and gap_worst <= tol,
    }
    log(f"correct: {checks} argmax_gap={gap_worst} tol={tol} "
        f"sampled={picks} programs_warm={programs_warm} "
        f"programs_end={programs_end} clock={w['clock_all']}")

    decode_steps = [s for s in steps if s[2] > 0]
    detail = {
        "requests_due": due_n, "requests_finished": sum(finished),
        "requests_failed": failed, "drained": w["drained"],
        "window_tokens": w["window_tokens"], "steps": len(steps),
        "ttft_p50_ms": 1e3 * stats.median(ttft),
        "ttft_max_ms": 1e3 * max(ttft), "ttft_samples": len(ttft),
        "itl_p50_ms": 1e3 * stats.median(gaps) if gaps else None,
        "itl_max_ms": 1e3 * max(gaps) if gaps else None,
        "itl_samples": len(gaps),
        "generator_lateness_p95_ms": 1e3 * stats.percentile(late, 0.95),
        "generator_lateness_max_ms": 1e3 * max(late),
        "batch_occupancy": w["occupancy"],
        "queue_depth_at_middle": w["queue_mid"],
        "queue_depth_at_end": w["queue_end"],
        "mean_active_slots": float(np.mean([s[2] for s in decode_steps]))
        if decode_steps else 0.0,
        "mean_live_kv_rows": float(np.mean([s[3] for s in decode_steps]))
        if decode_steps else 0.0,
        "window_programs": w["window_programs"], "argmax_gap": gap_worst,
        "kv_row_bytes": flops_bytes.kv_row_bytes(facts),
        "drain_s": w["t_drained"] - w["t_end"],
    }
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": due_n, "failed": failed,
        "setup_s": setup_s, "memory_peak_bytes": w["memory_peak"],
        "end_to_end": {
            "serve_tokens_per_s": w["window_tokens"] / (w["t_end"] - w["t0"]),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 0.95),
            "itl_p95_ms": 1e3 * stats.percentile(gaps, 0.95)
            if gaps else None,
        },
        "detail": detail,
        # what the per-layer readers take their numbers from
        "facts": facts, "num_slots": num_slots,
        "window": (w["t0"], w["t_end"]), "steps": steps, "spans": spans,
        "occupancy": w["occupancy"], "trace_clock": w["trace_clock"],
        "ttft_by_due": [(r.arrival.due_s, t)
                        for r, t in zip(w["live"], ttft)],
        "decode_module_prefix": mix["decode_module_prefix"],
    }
