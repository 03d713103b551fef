"""The decode program's sampling tail (``serving.engine.sample_rows``).

Each stage of the tail sits under a ``lax.cond`` on what the rows' own
sampling parameters ask for.  The contracts:
  * ``sample_rows`` is BIT-equal to the pipeline that runs every stage
    for every row (copied below as the plain reference, no conds), for
    greedy, sampling and mixed batches, jitted and eager;
  * a greedy row's token and carried key do not depend on whether its
    neighbours sample;
  * no sort, cumsum, vocabulary gather or random draw stands outside a
    ``cond`` in the traced tail;
  * through the engine: ``sampling_slots`` on every ``serving.step`` span
    says whether the sampling branch ran, ONE decode program is traced,
    and seeded runs still match ``generate(seed=...)``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.engine import _first_token, sample_rows

SHAPES = {"16x50304": (16, 50304), "4x97": (4, 97)}
ROWS = ("greedy", "sampling", "mixed")


# ------------------------------------------------- the plain reference
def _reference(keys, logits, do_sample, temperature, top_k, top_p,
               mask=None):
    """The tail as it stood before the conds: every stage, every row."""
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    greedy_tok = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    scaled = logits / temp[:, None]
    # per-row top-k by rank (argsort of the descending argsort)
    order = jnp.argsort(-scaled, axis=-1)
    rank = jnp.argsort(order, axis=-1)
    k = jnp.asarray(top_k, jnp.int32)[:, None]
    filtered = jnp.where(jnp.where(k > 0, rank < k, True), scaled,
                         -jnp.inf)
    # nucleus: keep a token while the mass before it is < p
    p = jnp.asarray(top_p, jnp.float32)[:, None]
    sorted_idx = jnp.argsort(-filtered, axis=-1)
    sorted_logits = jnp.take_along_axis(filtered, sorted_idx, -1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < p
    keep = jnp.take_along_axis(keep_sorted,
                               jnp.argsort(sorted_idx, axis=-1), -1)
    nucleus = jnp.where(keep, filtered, -jnp.inf)
    filtered = jnp.where(p >= 1.0, filtered, nucleus)
    sampled = jax.vmap(jax.random.categorical)(keys, filtered)
    return jnp.where(jnp.asarray(do_sample, bool), sampled, greedy_tok)


def _tail(fn, keys, logits, do_sample, temperature, top_k, top_p, mask):
    """The decode program's tail around ``fn``: split, sample from the
    second half, carry the first."""
    split = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
    tok = fn(split[:, 1], logits, do_sample, temperature, top_k, top_p,
             mask=mask)
    return tok, split[:, 0]


@functools.lru_cache(maxsize=None)
def _jitted(which):
    # the sampling parameters are traced operands, so one compile per
    # (shape, mask or none) serves every case
    return jax.jit(functools.partial(
        _tail, sample_rows if which == "gated" else _reference))


def _operands(shape, rows, top_k, top_p, with_mask, seed=0):
    n, vocab = SHAPES[shape]
    rs = np.random.RandomState(seed)
    # ties on purpose: logits rounded to a coarse grid
    logits = jnp.asarray(
        np.round(rs.randn(n, vocab) * 4.0, 1).astype(np.float32))
    keys = jnp.asarray(rs.randint(0, 2**31, (n, 2)).astype(np.uint32))
    do_sample = {"greedy": np.zeros(n, bool), "sampling": np.ones(n, bool),
                 "mixed": np.arange(n) % 3 == 1}[rows]
    temperature = (0.5 + rs.rand(n) * 1.5).astype(np.float32)
    # rows differ: every third row keeps the stage off
    k = np.where(np.arange(n) % 3 == 2, 0, top_k).astype(np.int32)
    p = np.where(np.arange(n) % 3 == 0, 1.0, top_p).astype(np.float32)
    mask = None
    if with_mask:
        m = rs.rand(n, vocab) < 0.7
        m[:, 0] = True
        m[0] = True                     # an unconstrained row
        mask = jnp.asarray(m)
    return (keys, logits, jnp.asarray(do_sample), jnp.asarray(temperature),
            jnp.asarray(k), jnp.asarray(p), mask)


@pytest.mark.parametrize("mode", ("jit", "eager"))
@pytest.mark.parametrize("shape", tuple(SHAPES))
@pytest.mark.parametrize("with_mask", (False, True),
                         ids=("nomask", "mask"))
@pytest.mark.parametrize("top_p", (1.0, 0.9))
@pytest.mark.parametrize("top_k", (0, 1, 5))
@pytest.mark.parametrize("rows", ROWS)
def test_bit_equal_to_ungated_pipeline(rows, top_k, top_p, with_mask,
                                       shape, mode):
    ops = _operands(shape, rows, top_k, top_p, with_mask)
    if mode == "jit":
        got = _jitted("gated")(*ops)
        want = _jitted("reference")(*ops)
    else:
        got = _tail(sample_rows, *ops)
        want = _tail(_reference, *ops)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if rows != "greedy" and top_k == 0 and shape == "16x50304":
        # the sampling rows really drew: not all of them the argmax
        ds = np.asarray(ops[2])
        masked = ops[1] if ops[6] is None else \
            jnp.where(ops[6], ops[1], -jnp.inf)
        assert np.any(np.asarray(got[0])[ds]
                      != np.asarray(jnp.argmax(masked, -1))[ds])


@pytest.mark.parametrize("mode", ("jit", "eager"))
def test_greedy_row_unmoved_by_sampling_neighbours(mode):
    """A greedy row among rows that sample with top_k AND top_p emits
    the token, and carries the key, it does among greedy rows."""
    keys, logits, _, temperature, _, _, mask = _operands(
        "16x50304", "greedy", 0, 1.0, True, seed=3)
    n = logits.shape[0]
    row = 5
    alone = (jnp.zeros(n, bool), jnp.zeros(n, jnp.int32),
             jnp.ones(n, jnp.float32))
    ds = np.ones(n, bool)
    ds[row] = False
    crowd = (jnp.asarray(ds), jnp.full(n, 7, jnp.int32),
             jnp.full(n, 0.8, jnp.float32))
    fn = _jitted("gated") if mode == "jit" else \
        functools.partial(_tail, sample_rows)
    tok_a, key_a = fn(keys, logits, alone[0], temperature, alone[1],
                      alone[2], mask)
    tok_c, key_c = fn(keys, logits, crowd[0], temperature, crowd[1],
                      crowd[2], mask)
    assert int(tok_a[row]) == int(tok_c[row])
    np.testing.assert_array_equal(np.asarray(key_a), np.asarray(key_c))
    # and the neighbours did sample
    assert np.any(np.asarray(tok_a) != np.asarray(tok_c))


# ------------------------------------------------------------ structure
HEAVY = ("sort", "cumsum", "gather", "random_bits", "threefry2x32")
VOCAB = SHAPES["4x97"][1]


def _walk(jaxpr, in_cond, found):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in HEAVY:
            # a gather counts where it touches the vocabulary axis
            if name != "gather" or max(
                    v.aval.size for v in eqn.invars) >= VOCAB:
                found.append((name, in_cond))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _walk(sub, in_cond or name == "cond", found)


@pytest.mark.parametrize("with_mask", (False, True),
                         ids=("nomask", "mask"))
def test_no_heavy_stage_outside_a_cond(with_mask):
    ops = _operands("4x97", "mixed", 5, 0.9, with_mask)
    found = []
    _walk(jax.make_jaxpr(sample_rows)(*ops).jaxpr, False, found)
    inside = {n for n, c in found if c}
    # the walk does see them: four sorts' worth, the cumsum, the two
    # permutation gathers and the draw all sit under a cond ...
    assert {"sort", "cumsum", "gather", "random_bits"} <= inside
    assert sum(1 for n, c in found if n == "sort") == 4
    # ... and none stands outside one
    assert [n for n, c in found if not c] == []
    # the same walk over the reference finds them all outside
    ref = []
    _walk(jax.make_jaxpr(_reference)(*ops).jaxpr, False, ref)
    assert ref and not any(c for _, c in ref)


# ----------------------------------------------------- through the engine
@pytest.fixture(scope="module")
def gpt():
    with jax.default_prng_impl("rbg"):
        return GPTForCausalLM(gpt_tiny())


def _prompts(seed, lengths, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (L,)) for L in lengths]


def _want(model, prompt, n, **kw):
    seq = model.generate(jnp.asarray(prompt)[None], max_new_tokens=n, **kw)
    return np.asarray(seq)[0, len(prompt):]


def _step_spans(eng):
    return eng.tracer.spans(lane=0, name="serving.step")


def test_engine_greedy_run_reads_zero_sampling_slots(gpt):
    eng = ServingEngine(gpt, num_slots=3, min_bucket=8)
    prompts = _prompts(0, (3, 7, 12, 5))
    outs = eng.serve_batch(prompts, max_new_tokens=5, max_steps=200)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(np.asarray(o.tokens),
                                      _want(gpt, p, 5))
    spans = _step_spans(eng)
    decoded = [s for s in spans if s.attrs["active_slots"] > 0]
    assert decoded and all("sampling_slots" in s.attrs for s in spans)
    assert all(s.attrs["sampling_slots"] == 0 for s in spans)
    assert eng.core.trace_counts["decode"] == 1


def test_engine_mixed_run_counts_sampling_requests_in_flight(gpt):
    """``sampling_slots`` is the number of occupied slots whose request
    samples; the greedy request's tokens are those of a greedy-only run
    and each seeded one matches ``generate(seed=...)``, all through ONE
    decode program."""
    eng = ServingEngine(gpt, num_slots=4, min_bucket=8)
    prompts = _prompts(2, (3, 7, 5, 9))
    new = (9, 4, 7, 6)
    kws = [dict(),
           dict(do_sample=True, temperature=2.0, seed=3),
           dict(do_sample=True, top_k=5, top_p=0.7, seed=4),
           dict(do_sample=True, top_p=0.9, seed=5)]
    seen = {}

    def cb(rid):
        return lambda tok, pos: seen.setdefault(rid, []).append(
            eng.core._step_in_flight)

    rids = [eng.submit(p, max_new_tokens=n, sampling=SamplingParams(**kw),
                       stream=cb(i))
            for i, (p, n, kw) in enumerate(zip(prompts, new, kws))]
    eng.run_until_complete(200)
    for rid, p, n, kw in zip(rids, prompts, new, kws):
        np.testing.assert_array_equal(
            np.asarray(eng.result(rid).tokens), _want(gpt, p, n, **kw))
    sampling = [i for i, kw in enumerate(kws) if kw]
    read = []
    for span in _step_spans(eng):
        idx = span.attrs["step"]
        if span.attrs["active_slots"] == 0:
            assert span.attrs["sampling_slots"] == 0
            continue
        # in a slot at this step's decode dispatch: first token out in a
        # step <= idx, last token not before idx
        live = [i for i in sampling
                if seen[i][0] <= idx and seen[i][-1] >= idx]
        assert span.attrs["sampling_slots"] == len(live), idx
        assert span.attrs["sampling_slots"] <= span.attrs["active_slots"]
        read.append(span.attrs["sampling_slots"])
    # all three sampled at once, and the greedy request outlived them
    assert max(read) == 3 and read[-1] == 0
    assert eng.core.trace_counts["decode"] == 1


def test_first_token_tail_is_one_program(gpt):
    """The first token of every completed prefill goes through ONE
    jitted program whatever the prompt's width and the request's
    sampling parameters: an eager ``lax.cond`` would compile per call
    (inside a benchmark's measured window)."""
    eng = ServingEngine(gpt, num_slots=3, min_bucket=8)
    eng.serve_batch(_prompts(4, (5,)), max_new_tokens=2, max_steps=50)
    warm = _first_token._cache_size()
    prompts = _prompts(5, (3, 9, 17, 30))
    kws = [dict(), dict(do_sample=True, top_k=3, seed=1),
           dict(do_sample=True, top_p=0.8, seed=2), dict()]
    rids = [eng.submit(p, max_new_tokens=3, sampling=SamplingParams(**kw))
            for p, kw in zip(prompts, kws)]
    eng.run_until_complete(200)
    for rid, p, kw in zip(rids, prompts, kws):
        np.testing.assert_array_equal(
            np.asarray(eng.result(rid).tokens), _want(gpt, p, 3, **kw))
    assert _first_token._cache_size() == warm
