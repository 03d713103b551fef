"""The one general traffic generator.  A traffic mix is a data file
(``benchmarks/traffic/<mix>.json``) of parameters; nothing about a mix
lives in code.

Steadiness by construction: the sizes and arrival times of a mix are ONE
fixed sample (drawn from the file's ``base_seed``), so every ``--seed``
serves requests of the same lengths at the same instants; the run seed
decides every token (and, in the drivers, every weight).  A tail is set
by the few worst coincidences of a long prompt with a full batch: with
the order left to the seed, two seeds measure two different queues, and
no bound tighter than their difference could hold.
"""

import dataclasses

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _lognormal_int(rs, spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers, lognormal with the given median and sigma,
    clipped to ``[min, max]``."""
    draw = rs.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(draw), spec["min"], spec["max"]).astype(np.int64)


def request_count(params: dict, seconds: float) -> int:
    return max(int(round(params["rate_per_s"] * seconds)), 1)


def open_loop_schedule(params: dict, seed: int, seconds: float,
                       vocab: int) -> list:
    """The requests due inside a window of ``seconds``, in due order: a
    pure function of ``(params, seed, seconds, vocab)``.

    ``rate_per_s * seconds`` requests.  Gaps are exponential (Poisson
    arrivals) and rescaled so that the last request is due just inside
    the window; prompt and output lengths are lognormal and clipped.
    All three come from ``base_seed``; ``seed`` draws the prompts'
    tokens (uniform over the vocabulary, so no two prompts share a
    prefix the cache could serve)."""
    n = request_count(params, seconds)
    base = np.random.RandomState(params["base_seed"])
    prompt_len = _lognormal_int(base, params["prompt_len"], n)
    output_len = _lognormal_int(base, params["output_len"], n)
    gaps = base.exponential(1.0, n + 1)     # the last is the room left
    due = np.cumsum(gaps[:n]) * (seconds / gaps.sum())
    rs = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    return [Arrival(float(due[i]),
                    rs.integers(0, vocab, int(prompt_len[i]), dtype=np.int32),
                    int(output_len[i]))
            for i in range(n)]


def corpus(params: dict, seed: int, vocab: int) -> np.ndarray:
    """``[batches, batch, seq + 1]`` int32 token ids from ``seed``: the
    training corpus a job cycles through in a fixed order."""
    rs = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    return rs.integers(
        0, vocab, (params["corpus_batches"], params["batch"],
                   params["seq"] + 1), dtype=np.int32)
