"""The comparison that decides ``correct``: the system's outputs against
the family's plain float32 reference (``builders/<family>.reference_
forward``) at ``highest`` matmul precision, on the same seeded weights.

Tolerances and their reasons are copied from ``chip_smoke.py`` (measured
on the chip in PR 21).  bf16 keeps 8 significant bits, one rounding is
2**-9 relative, and the logits are sums over thousands of such terms
through every layer.  Agreement was judged there on max|system - reference| over
max|reference| of a logit vector: 0.0057-0.0074 measured on a v5e at
GPT-3 6.7B width, four layers.  Four times that is the bar: a format with
5 significant bits instead of 8 would sit near 0.05, a wrong mask or
position near 1.
"""

import jax
import jax.numpy as jnp
import numpy as np

BF16_LOGIT_TOL = 0.03
# float32 systems (the CPU rehearsal) differ from the reference only by
# summation order
F32_LOGIT_TOL = 2e-4
# a mean cross-entropy over thousands of tokens averages the logits'
# rounding away: the bf16 system's loss on batch 0 sat within 1e-6 of the
# reference's 10.4927 in every chip run of PR 24 (mistral-7b-d2, 8192
# tokens; PERF.md).  One bf16 rounding of a logit is 2**-9 relative, and
# 0.02 absolute allows a systematic bias of that size on a loss of 10;
# a dropped layer, a wrong mask or rotary pairing moves the loss by 0.1+
BF16_LOSS_TOL = 0.02
F32_LOSS_TOL = 1e-4


def logit_tol(dtype) -> float:
    return BF16_LOGIT_TOL if jnp.dtype(dtype) == jnp.bfloat16 \
        else F32_LOGIT_TOL


def loss_tol(dtype) -> float:
    return BF16_LOSS_TOL if jnp.dtype(dtype) == jnp.bfloat16 \
        else F32_LOSS_TOL


def _f32(params):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)


def reference_logits(builder, cfg: dict, params: dict, seqs) -> list:
    """One ``[len, vocab]`` float32 array per 1-D token sequence of
    ``seqs``: the plain forward on a float32 copy of ``params``.
    Sequences run one at a time, right-padded to a multiple of 128 so
    few programs compile; causal attention keeps the padding out of
    every real position."""

    @jax.jit
    def forward(p, ids):
        with jax.default_matmul_precision("highest"):
            return builder.reference_forward(cfg, _f32(p), ids)

    out = []
    for s in seqs:
        width = -(-len(s) // 128) * 128
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(s)] = s
        out.append(np.asarray(forward(params, jnp.asarray(ids)))[0, :len(s)])
    return out


def reference_loss(builder, cfg: dict, params: dict, x, y) -> float:
    """Mean next-token cross-entropy of the plain forward on ``x
    [b, s]`` against labels ``y [b, s]``, one row at a time."""

    @jax.jit
    def row_loss(p, ids, labels):
        with jax.default_matmul_precision("highest"):
            logits = builder.reference_forward(cfg, _f32(p), ids)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    x, y = np.asarray(x), np.asarray(y)
    return float(np.mean([float(row_loss(params, jnp.asarray(x[i:i + 1]),
                                         jnp.asarray(y[i:i + 1])))
                          for i in range(len(x))]))


def argmax_gap(ref, tokens) -> float:
    """How far below the reference's best logit the emitted tokens sit,
    over max|reference|: ``ref [n, vocab]`` are the reference logits at
    the positions that produced ``tokens [n]``.  Sampled tokens flip on
    rounding with random weights; the reference logit of the token the
    system chose cannot be far from the top."""
    ref = np.asarray(ref, np.float32)
    chosen = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(ref.max(-1) - chosen) / np.max(np.abs(ref)))
