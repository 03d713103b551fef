"""The decode program reads the KV slabs where they lie.

The copy this guards against cost half of a decode step (PERF.md,
PR 29): ``decode_attention`` re-laid every slot slab head-major ahead of
its kernel, the looped model sliced each plane out of its slab first,
and a GQA model repeated the cache up to its query heads.  A number
cannot be had here, but the PROGRAM can: each case lowers the engine's
own decode program for the chip (the routes are the chip's; nothing is
compiled or run) at a tiny depth and the published head size, and looks
through its StableHLO for any data-movement operation whose result is
as large as a slab, a plane, or a plane repeated up to the query heads.

Since PR 31 the in-place kernel also WRITES the step's fresh rows, so
such a program holds no XLA append either (PERF.md, PR 31: 384 loops of
8 one-row updates were a quarter of the looped model's step): no
``scatter`` or ``dynamic_update_slice`` with a slab-sized result, and no
``while`` that carries a slab but the model's own scans.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

SLOTS, ROWS, DH = 4, 48, 128
_MOVES = re.compile(
    r"stablehlo\.(transpose|copy|dynamic_slice|broadcast\w*|concatenate)\b"
    r".*->\s*tensor<([0-9x]+)x\w+>")


_RESULT = re.compile(r"->\s*tensor<([0-9x]+)x\w+>")
_TENSOR = re.compile(r"tensor<([0-9x]+)x\w+>")


def _gpt(heads, head_dim):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=heads * head_dim, num_layers=2,
        num_heads=heads, max_seq_len=ROWS, ffn_mult=1, remat=False))


def _llama_gqa():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=8 * DH, intermediate_size=64,
        num_layers=2, num_heads=8, num_kv_heads=2, max_seq_len=ROWS))


def _ouro():
    from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
    return OuroForCausalLM(OuroConfig(
        vocab_size=96, hidden_size=8 * DH, intermediate_size=64,
        num_layers=2, num_heads=8, max_seq_len=ROWS, total_ut_steps=2))


def _decode_stablehlo(model):
    """StableHLO of the engine's ONE decode program, lowered for the
    TPU with its own operands (the AOT builder's list)."""
    from paddle_tpu.serving import ServingEngine
    core = ServingEngine(model, num_slots=SLOTS, min_bucket=8,
                         max_seq=ROWS).core
    program = core._build_decode_fn()
    n, vocab = core.num_slots, int(model.cfg.vocab_size)
    args = (core.pool.ks, core.pool.vs, core.pool.seq_pos,
            jnp.zeros((n,), jnp.int32),
            jnp.tile(jax.random.PRNGKey(0)[None], (n, 1)),
            jnp.zeros((n,), bool), jnp.ones((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.float32),
            jnp.ones((n, vocab), bool))
    text = program.func.trace(*program.args, *args).lower(
        lowering_platforms=("tpu",)).as_text()
    return core, text


def _slab_sized_moves(text, sizes):
    found = []
    for line in text.splitlines():
        m = _MOVES.search(line)
        if m and int(np.prod([int(d) for d in m.group(2).split("x")])) \
                in sizes:
            found.append(line.strip()[:160])
    return found


def _size(dims: str) -> int:
    return int(np.prod([int(d) for d in dims.split("x")]))


def _slab_sized_appends(text, sizes):
    """``(updates, loops)``: the ``scatter`` / ``dynamic_update_slice``
    operations whose result is slab-sized (a vmapped per-row
    ``dynamic_update_slice`` lowers to a ``scatter``, which XLA:TPU runs
    as a loop of one-row updates), and the ``while`` loops that carry a
    slab-sized value."""
    updates, loops = [], []
    lines = text.splitlines()
    for i, line in enumerate(lines):
        if "stablehlo.while" in line:
            carried = _TENSOR.findall(line.rsplit(") :", 1)[-1])
            if any(_size(d) in sizes for d in carried):
                loops.append(line.strip()[:80])
        elif re.search(r"stablehlo\.(scatter|dynamic_update_slice)\b",
                       line):
            # a scatter's type follows its region, lines further down
            typed = next(ln for ln in lines[i:] if _RESULT.search(ln))
            if _size(_RESULT.search(typed).group(1)) in sizes:
                updates.append(line.strip()[:80])
    return updates, loops


@pytest.mark.parametrize("make,route,copies,scans", [
    (lambda: _gpt(8, DH), "slab_in_place", False, 0),
    # the looped model's two scans (passes, layers) carry its one slab
    (_ouro, "slab_in_place", False, 2),
    (_llama_gqa, "slab_in_place", False, 0),
    # the guards have teeth: 16 x 64 is a slab Mosaic cannot window, the
    # copying kernel serves it behind the XLA append, and its
    # transposes and its scatters (K and V of two layers) are found
    (lambda: _gpt(16, 64), "head_major_copy", True, 0),
], ids=["gpt3_shaped", "ouro_shaped", "llama_gqa", "gpt_h64_copies"])
def test_decode_program_moves_no_slab(make, route, copies, scans,
                                      monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = make()
    core, text = _decode_stablehlo(model)
    assert core.attention_route()[0] == route, core.attention_route()
    in_place = route == "slab_in_place"
    assert core.kv_append() == (
        ("in_kernel", None) if in_place
        else ("xla_scatter", core.attention_route()[1]))
    cfg = model.cfg
    slab = core.pool.ks[0]
    kv_heads = getattr(cfg, "kv_heads", None) or cfg.num_heads
    plane = slab.size // slab.shape[2] * kv_heads
    sizes = {slab.size, plane, plane * (cfg.num_heads // kv_heads)}
    found = _slab_sized_moves(text, sizes)
    updates, loops = _slab_sized_appends(text, {slab.size})
    assert text.count("tpu_custom_call") >= 1
    assert len(loops) == scans, loops
    if copies:
        assert any("transpose" in line for line in found), found
        assert len(updates) == 2 * cfg.num_layers, updates
    else:
        assert not found, "\n".join(found)
        assert not updates, "\n".join(updates)


@pytest.mark.parametrize("spec_k", [0, 2], ids=["decode", "verify"])
def test_free_slots_stay_parked_at_row_zero(spec_k):
    """The in-place kernel streams the rows ``seq_lens`` calls live, so
    a free slot must not grow a phantom length while the others decode
    (it rode along a row a step, and the kernel read all of it)."""
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ServingEngine
    model = GPTForCausalLM(gpt_tiny())
    eng = ServingEngine(model, num_slots=3, min_bucket=8, max_seq=64,
                        spec_k=spec_k)
    prompt = np.tile([5, 6, 7, 8], 3)
    short = eng.submit(prompt, max_new_tokens=2)
    long_ = eng.submit(prompt[:9], max_new_tokens=12)
    while eng.step():
        pos = np.asarray(eng.core.pool.seq_pos)
        live = set(eng.core._slots)
        assert all(pos[s] == 0 for s in range(3) if s not in live), pos
    want = np.asarray(model.generate(prompt[None, :9],
                                     max_new_tokens=12))[0, 9:]
    assert list(eng.result(long_).tokens) == list(want)
    assert eng.result(short).finished


# ------------------------------------------------- held to the parent's

# sha256 of the StableHLO of the engine's decode program and of its
# 16-wide prefill program at the models above, lowered for the TPU, as
# PR 31's tree (869de36) lowers them under this suite's conftest
# (``highest`` matmul precision, eight virtual devices).  A Pallas kernel's serialized body
# carries the checkout's path in its locations, so each custom call's
# ``backend_config`` is blanked before hashing (the kernels' own files
# are what they were).  PR 32 threads a recurrent state through both
# programs for the model that declares one: a model that declares none
# is called without the operand and must lower to the program it always
# did.  A PR that means to change these programs re-pins them and says
# what moved.
_PARENT_SHA256 = {
    ("gpt", "decode"):
    "d55080e61d8782bd77fad04eaf1509d6f9b6089cdd3306a02bb3be4872ea7f5a",
    ("gpt", "prefill"):
    "c3d853159b67a46c25c8bfb1e013117a75a6f7ed76e2d9a08bcb5a14f132f81d",
    ("llama", "decode"):
    "493c0f9bc34750d03113c00c002cab0c49086a47aaf9ed36d8c244159f3922a1",
    ("llama", "prefill"):
    "3295d651c084c24978f19776ceffac80c4e8391e2e3866109e6e22493b9da6fc",
    ("ouro", "decode"):
    "517f7023dd6d249a34a8037d68633b0ab9affd4c2921221132f66157f31b3e10",
    ("ouro", "prefill"):
    "4d9e9163e842dfa48352063bb342dfd573ea06f23a94128d9217d1b7083f29e0",
}
_KERNEL_BODY = re.compile(r'backend_config = "(?:[^"\\]|\\.)*"')


def _prefill_stablehlo(core, width=16, valid=9):
    program = core._build_prefill_fn()
    ks, vs = core._build_staging_init_fn()()
    return program.func.trace(
        *program.args, ks, vs, jnp.zeros((1, width), jnp.int32),
        jnp.asarray(0, jnp.int32), jnp.asarray(valid, jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("family, program", sorted(_PARENT_SHA256))
def test_a_stateless_models_programs_lower_as_on_the_parent(
        family, program, monkeypatch):
    import hashlib
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = {"gpt": lambda: _gpt(8, DH), "llama": _llama_gqa,
             "ouro": _ouro}[family]()
    core, text = _decode_stablehlo(model)
    assert not core._stateful and core.pool.state == ()
    if program == "prefill":
        text = _prefill_stablehlo(core)
    digest = hashlib.sha256(
        _KERNEL_BODY.sub('backend_config = ""', text).encode()).hexdigest()
    assert digest == _PARENT_SHA256[family, program]
