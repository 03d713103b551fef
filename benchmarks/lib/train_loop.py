"""The measured window of a training cell, shared by the kinds of
training run: steps dispatched as a trainer does, the clock read only
where the loop waits for the device."""

import time

import numpy as np

from benchmarks.lib import flops_bytes, stats, xplane
from benchmarks.lib.compile_clock import CompileClock


def run_window(ctx, step, state, put, batches: int, tokens_per_step: int,
               sync_every: int, trace_steps: int) -> dict:
    """``step(state, x, y) -> (state, loss)``; ``put(k) -> (x, y)`` puts
    batch ``k`` of the corpus on the device.  Two warm-up steps run
    first (set-up).  Untraced: steps are dispatched without waiting,
    except at every ``sync_every``-th, where the loop waits for that
    step's loss and reads the clock; the last group is sized to end at
    ``ctx.seconds``.  Traced: every step is closed by
    ``block_until_ready`` and timed, and ``trace_steps`` of them in the
    middle of the window run under the profiler.  Losses stay on the
    device until the window has closed."""
    import jax
    k = 0
    warm = []
    for _ in range(2):
        state, loss = step(state, *put(k % batches))
        warm.append(loss)
        k += 1
    jax.block_until_ready(warm)

    losses, closed_s = [], []
    trace_clock = None
    with CompileClock() as clock:
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_start
        if not ctx.trace:
            per_step, group = None, sync_every
            while group > 0:
                for _ in range(group):
                    state, loss = step(state, *put(k % batches))
                    losses.append(loss)
                    k += 1
                loss.block_until_ready()
                elapsed = time.perf_counter() - t0
                per_step = elapsed / len(losses)
                room = int((ctx.seconds - elapsed) / per_step)
                group = min(sync_every, max(room, 0))
        else:
            tracing = xplane.TraceSlice(ctx.trace_dir)
            traced_from = None
            while True:
                elapsed = time.perf_counter() - t0
                if elapsed >= ctx.seconds:
                    break
                if traced_from is None and elapsed >= ctx.seconds / 2:
                    tracing.start()
                    traced_from = len(losses)
                tb = time.perf_counter()
                state, loss = step(state, *put(k % batches))
                loss.block_until_ready()
                closed_s.append(time.perf_counter() - tb)
                losses.append(loss)
                k += 1
                if tracing.running \
                        and len(losses) - traced_from >= trace_steps:
                    tracing.stop()
            if tracing.running:
                tracing.stop()
            trace_clock = tuple(tracing.clock)
        t_end = time.perf_counter()
        window_programs = clock.snapshot()
    memory_peak = ctx.memory_peak()
    losses = [float(v) for v in jax.device_get(losses)]
    nonfinite = int(np.sum(~np.isfinite(losses)))
    # the corpus is cycled in a fixed order: compare whole passes where
    # the window held two, single steps where it did not
    span = batches if len(losses) >= 2 * batches else 1
    first, last = np.mean(losses[:span]), np.mean(losses[-span:])
    window_s = t_end - t0
    return {
        "state": state, "setup_s": setup_s, "memory_peak_bytes": memory_peak,
        "steps": len(losses), "nonfinite": nonfinite,
        "loss_first_pass": float(first), "loss_last_pass": float(last),
        "window_s": window_s,
        "tokens_per_s": len(losses) * tokens_per_step / window_s,
        "window_programs": window_programs,
        "closed_step_ms": 1e3 * stats.median(closed_s) if closed_s else None,
        "closed_steps": closed_s,
        "checks": {"losses_finite": nonfinite == 0,
                   "loss_falls": bool(last < first),
                   "no_program_in_window": window_programs["programs"] == 0},
        "trace_clock": trace_clock,
    }


def report(ctx, res: dict, facts: dict, seq: int, tokens_per_step: int,
           checks: dict, step_module_prefix: str, **detail) -> dict:
    """What a training driver returns: ``run_window``'s readings as the
    end-to-end metric, the earlier lines' detail (MFU under both counts,
    named for what they are) and what the per-layer readers take."""
    per_chip = res["tokens_per_s"] / ctx.chips
    flops = {c: flops_bytes.train_flops_per_token(facts, seq, c)
             for c in ("required", "6n12les")}
    peak = ctx.peaks["bf16_flops"]
    ctx.log(f"correct: {checks}")
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": res["steps"], "failed": res["nonfinite"],
        "setup_s": res["setup_s"],
        "memory_peak_bytes": res["memory_peak_bytes"],
        "end_to_end": {"train_tokens_per_s": per_chip},
        "detail": {
            "steps": res["steps"], "window_s": res["window_s"],
            "loss_first_pass": res["loss_first_pass"],
            "loss_last_pass": res["loss_last_pass"],
            "params": flops_bytes.total_params(facts),
            "flops_per_token": flops,
            "mfu_required": per_chip * flops["required"] / peak,
            "mfu_6n12les": per_chip * flops["6n12les"] / peak,
            "window_programs": res["window_programs"],
            "closed_step_ms": res["closed_step_ms"], **detail},
        "facts": facts, "seq": seq, "tokens_per_step": tokens_per_step,
        "closed_steps": res["closed_steps"],
        "trace_clock": res["trace_clock"],
        "step_module_prefix": step_module_prefix,
    }
