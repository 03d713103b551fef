"""Standalone recompute (activation checkpointing) parity functions.

Reference: python/paddle/distributed/fleet/recompute/recompute.py —
RecomputeFunction (a PyLayer stashing inputs, re-running forward during
backward with the RNG-state tracker restored so dropout masks match) and
recompute_sequential.

TPU-native: jax.checkpoint IS the recompute engine — it rematerializes the
wrapped computation in the backward pass, and because JAX RNG is explicit
(keys are values, threaded by rng_context / RNGStatesTracker), replayed
dropout draws the SAME mask by construction: no state juggling needed.
``preserve_rng_state`` is therefore accepted and always true in effect.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax

__all__ = ["recompute", "recompute_sequential", "remat_wrap",
           "resolve_remat_policy", "remat_from_env"]

_POLICY_NAMES = ("dots_saveable", "nothing_saveable",
                 "dots_with_no_batch_dims_saveable",
                 "everything_saveable", "checkpoint_dots",
                 "checkpoint_dots_with_no_batch_dims")


def resolve_remat_policy(name: str):
    """jax.checkpoint_policies entry for ``name`` — the ONE resolver for
    every remat knob (model configs, Engine strategy).  Unknown
    names raise with the known list (silent fallback to full checkpoint
    would invalidate memory/perf comparisons)."""
    # allowlist, not getattr: jax.checkpoint_policies also exposes
    # argument-taking FACTORIES (save_only_these_names, ...) which are not
    # policies themselves — passing one to jax.checkpoint silently saves
    # everything, exactly the misconfiguration this resolver must prevent
    if name not in _POLICY_NAMES:
        raise ValueError(
            f"unknown remat policy {name!r}; known: {', '.join(_POLICY_NAMES)}"
            " (or True for full checkpoint, False for none)")
    return getattr(jax.checkpoint_policies, name)


def remat_from_env(var: str = "BENCH_REMAT", default: str = "0"):
    """Env parsing of a remat knob: '0' -> False, '1' -> True (full
    checkpoint), anything else -> policy name.  No caller in the tree
    (ROADMAP D13)."""
    import os
    v = os.environ.get(var, default)
    return True if v == "1" else (False if v == "0" else v)


def remat_wrap(fn: Callable, remat) -> Callable:
    """Apply the remat knob: False -> fn; True -> full jax.checkpoint;
    a string names a jax.checkpoint_policies policy."""
    if not remat:
        return fn

    # a fresh function per wrap: jax.checkpoint caches its trace on the
    # function's identity, and a Layer's parameters are closed over, not
    # passed — a second trace of the same block (another jit of the same
    # model at the same shapes) would otherwise be handed the first
    # trace's jaxpr with that trace's parameter tracers inside it
    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    if isinstance(remat, str):
        return jax.checkpoint(call, policy=resolve_remat_policy(remat))
    return jax.checkpoint(call)


def recompute(function: Callable, *args, **kwargs):
    """Reference: fleet.utils.recompute(fn, *args) — run fn now, recompute
    its activations during backward.

    Accepted kwargs (parity): ``use_reentrant`` (ignored; jax.checkpoint
    has one semantics), ``preserve_rng_state`` (always effectively True).
    """
    kwargs.pop("use_reentrant", None)
    kwargs.pop("preserve_rng_state", None)
    policy = kwargs.pop("checkpoint_policy", None)
    fn = jax.checkpoint(function, policy=policy)
    return fn(*args, **kwargs)


def recompute_sequential(ctx: dict, functions: Sequence[Callable], *args):
    """Reference: recompute_sequential({'segments': k}, nn.Sequential(...))
    — checkpoint a layer list in k segments."""
    segments = int(ctx.get("segments", 1)) if ctx else 1
    funcs = list(functions)
    n = len(funcs)
    per = max(n // max(segments, 1), 1)

    def seg_fn(fs):
        def run(*xs):
            out = xs
            for f in fs:
                out = f(*out) if isinstance(out, tuple) else f(out)
                out = out if isinstance(out, tuple) else (out,)
            return out[0] if len(out) == 1 else out
        return run

    out = args
    i = 0
    while i < n:
        fs = funcs[i:i + per]
        out = out if isinstance(out, tuple) else (out,)
        out = (recompute(seg_fn(fs), *out),)
        i += per
    return out[0]
