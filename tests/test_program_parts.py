"""Device time by model part (PR 36): the scopes the programs carry and
the table a compiled program is read into.

Every jitted program of the five model families opens
``jax.named_scope(<part>)`` where a part's work happens
(``paddle_tpu.obs.parts.PARTS``).  Here, on the CPU at tiny sizes: every
matmul and kernel of the engine's decode and prefill programs and of a
training step sits under exactly one part; the parser reads literal
``op_name``s as documented; a traced engine records one ``program.parts``
span per compiled program and keeps them over ``metrics.reset()``; and
the scopes add no equation to a program.
"""

import contextlib
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.obs import Tracer, parts
from paddle_tpu.obs.parts import (PARTS, UNSCOPED, operation_key, part_of,
                                  program_name, program_parts)
from paddle_tpu.serving import ServingEngine

MATMULS = ("dot_general", "ragged_dot", "ragged_dot_general",
           "pallas_call")


def _gpt():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    return GPTForCausalLM(gpt_tiny()), {}


def _llama():
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    return LlamaForCausalLM(llama_tiny()), {}


def _ouro():
    from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny
    return OuroForCausalLM(ouro_tiny()), {"max_seq": 64}


def _jamba():
    from paddle_tpu.models.jamba import JambaForCausalLM, jamba_tiny
    return JambaForCausalLM(jamba_tiny()), {
        "max_seq": 96, "prefill_chunk": 16, "enable_prefix_cache": False}


def _deepseek():
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM,
                                               deepseek_v3_tiny)
    return DeepseekV3ForCausalLM(deepseek_v3_tiny()), {
        "max_seq": 96, "prefill_chunk": 16, "enable_prefix_cache": False}


FAMILIES = {"gpt": _gpt, "llama": _llama, "ouro": _ouro, "jamba": _jamba,
            "deepseek_v3": _deepseek}


def _annotate(name):
    return contextlib.nullcontext()


def _traced_engine(family):
    model, kw = FAMILIES[family]()
    return ServingEngine(model, num_slots=3, min_bucket=8,
                         tracer=Tracer(annotate=_annotate), **kw)


def _serve(eng, new_tokens=3):
    rid = eng.submit(np.arange(1, 13, dtype=np.int32),
                     max_new_tokens=new_tokens)
    while eng.step():
        pass
    assert eng.result(rid).status == "finished"


def _program_jaxprs(family) -> dict:
    """``{"prefill": jaxpr, "decode": jaxpr}`` of the engine's own
    programs over its own operands: taken where a traced engine wraps
    a program to read its parts, at the first dispatch."""
    eng = _traced_engine(family)
    taken = {}

    class Take:
        def __init__(self, program):
            self.program = program

        def __call__(self, *args):
            fn = self.program
            taken.setdefault(fn.func.__name__,
                             fn.func.trace(*fn.args, *args).jaxpr)
            return fn(*args)

    eng.core._with_parts = Take
    _serve(eng)
    eng.close()
    assert set(taken) == {"prefill", "decode"}
    return taken


@pytest.fixture(scope="module")
def programs():
    cache = {}

    def get(family):
        if family not in cache:
            cache[family] = _program_jaxprs(family)
        return cache[family]
    return get


def _train_step_jaxpr(family):
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.meta_parallel.mp_layers import \
        parallel_cross_entropy
    from paddle_tpu.nn.functional_call import functional_call, state
    if family == "gpt":
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny
        model = GPTForCausalLM(gpt_tiny(remat=True))
    else:
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        model = LlamaForCausalLM(llama_tiny(remat=True))
    params, buffers = state(model)
    o = opt.AdamW(learning_rate=1e-3)

    def loss_of(p, x, y):
        out, _ = functional_call(model, p, buffers, (x,), train=True)
        return jnp.mean(parallel_cross_entropy(out, y))

    def step(p, os_, x, y):
        loss, g = jax.value_and_grad(loss_of)(p, x, y)
        newp, nos = o.update(g, os_, p)
        return newp, nos, loss

    ids = jnp.zeros((2, 16), jnp.int32)
    return jax.make_jaxpr(step)(params, o.init(params), ids, ids)


def _matmul_paths(jaxpr, prefix=""):
    """``(primitive, op_name path)`` of every matmul and kernel of
    ``jaxpr`` and the jaxprs its equations hold (a kernel's own body
    aside): an equation's name stack continues its holder's, as the
    lowering joins them into the ``op_name``."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        path = "/".join(p for p in (prefix, stack) if p)
        if eqn.primitive.name in MATMULS:
            yield eqn.primitive.name, path
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _matmul_paths(sub, path)


def _assert_one_part_each(jaxpr, expect):
    found = list(_matmul_paths(jaxpr.jaxpr))
    assert found
    for prim, path in found:
        named = parts.parts_on_path(path)
        assert len(named) == 1, (prim, path, named)
    seen = {part_of(path)[0] for _, path in found}
    assert expect <= seen, (expect, seen)
    return found


# ---- (a) every matmul and kernel under exactly one part -----------------
_EXPECT = {
    "gpt": {"attention", "mlp", "head"},
    "llama": {"attention", "mlp", "head"},
    "ouro": {"attention", "mlp", "head", "exit_gate"},
    "jamba": {"attention", "mixer", "mlp", "head"},
    "deepseek_v3": {"attention", "mlp", "router", "experts",
                    "shared_expert", "head"},
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_program_matmuls_have_one_part(family, program, programs):
    _assert_one_part_each(programs(family)[program], _EXPECT[family])


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_train_step_matmuls_have_one_part(family):
    found = _assert_one_part_each(_train_step_jaxpr(family),
                                  {"attention", "mlp", "head"})
    phases = {part_of(path)[1] for _, path in found}
    assert phases == set(parts.PHASES)


def test_train_step_names_loss_and_optimizer():
    """The loss and the update hold no matmul: look at every equation."""
    def every(jaxpr, prefix=""):
        for eqn in jaxpr.eqns:
            stack = str(eqn.source_info.name_stack)
            path = "/".join(p for p in (prefix, stack) if p)
            yield part_of(path)[0]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from every(sub, path)
    seen = set(every(_train_step_jaxpr("llama").jaxpr))
    assert {"embed", "norm", "loss", "optimizer"} <= seen, seen


# ---- (b) the parser on literal op_names ---------------------------------
@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/transpose(jvp(layer0))/mlp/mul", ("mlp", "backward")),
    ("jit(step)/transpose(jvp(mlp))/dot_general", ("mlp", "backward")),
    ("jit(step)/jvp(attention)/flash_attention_fwd/pallas_call",
     ("attention", "forward")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "attention/norm/rsqrt", ("norm", "recomputed")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/mlp/dot_general",
     ("mlp", "backward")),
    # two names of the vocabulary: the innermost wins
    ("jit(decode)/attention/kv_append/scatter", ("kv_append", "forward")),
    ("jit(decode)/mixer/norm/mul", ("norm", "forward")),
    # none
    ("jit(step)/jvp()/sub", (UNSCOPED, "forward")),
    ("", (UNSCOPED, "forward")),
    # a function's name is no scope
    ("jit(norm)/jit(head)/add", (UNSCOPED, "forward")),
    ("jit(decode)/while/body/closed_call/experts/gmm/pallas_call",
     ("experts", "forward")),
])
def test_part_of(op_name, want):
    assert part_of(op_name) == want


def test_vocabulary_is_plain_strings():
    assert len(set(PARTS)) == len(PARTS)
    assert all(isinstance(p, str) and p.isidentifier() for p in PARTS)
    assert UNSCOPED not in PARTS


_HLO = """HloModule jit_toy, is_scheduled=true, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_computation (param_0: f32[8,8]) -> f32[8,8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  %inner.1 = f32[8,8]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(toy)/norm/mul"}
  ROOT %tanh.0 = f32[8,8]{1,0} tanh(%inner.1), metadata={op_name="jit(toy)/mlp/tanh" stack_frame_id=3}
}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %fetch.5 = f32[8,8]{1,0:S(1)} fusion(%gte), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(toy)/while/body/dynamic_slice"}
  %view.6 = f32[8,8]{1,0:S(1)} bitcast(%fetch.5)
  %dot.3 = f32[8,8]{1,0:T(8,128)} dot(%gte, %view.6), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(toy)/while/body/attention/dot_general"}
  %shared.7 = f32[8,8]{1,0} copy(%gte)
  %tanh.8 = f32[8,8]{1,0} tanh(%shared.7), metadata={op_name="jit(toy)/while/body/mlp/tanh"}
  %add.9 = f32[8,8]{1,0} add(%shared.7, %dot.3), metadata={op_name="jit(toy)/while/body/attention/add"}
  ROOT %tuple.9 = (s32[], f32[8,8]{1,0}) tuple(%gte, %add.9, %tanh.8)
}

ENTRY %main.1 (x.1: f32[8,8]) -> f32[8,8] {
  %x.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %copy.2 = f32[8,8]{0,1} copy(%x.1)
  %while.4 = (s32[], f32[8,8]{1,0}) while(%x.1), condition=%cond, body=%body, metadata={op_name="jit(toy)/while"}
  %fusion.7 = f32[8,8]{1,0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/mlp/tanh"}
  ROOT %bare_fusion = f32[8,8]{1,0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation
}
"""


def test_program_parts_reads_optimized_text():
    assert program_name(_HLO) == "jit_toy"
    table = program_parts(_HLO)
    assert table == {
        # a while's body is on the trace operation by operation
        "%dot.3 = f32[8,8]{1,0:T(8,128)}": ("attention", "forward"),
        "%add.9 = f32[8,8]{1,0}": ("attention", "forward"),
        "%tanh.8 = f32[8,8]{1,0}": ("mlp", "forward"),
        # under no scope, taken (through a bitcast) by one part alone:
        # the weight's fetch is its matmul's
        "%fetch.5 = f32[8,8]{1,0:S(1)}": ("attention", "forward"),
        # taken by two parts: nobody's
        "%shared.7 = f32[8,8]{1,0}": (UNSCOPED, "forward"),
        "%copy.2 = f32[8,8]{0,1}": (UNSCOPED, "forward"),
        "%while.4 = (s32[], f32[8,8]{1,0})": (UNSCOPED, "forward"),
        "%fusion.7 = f32[8,8]{1,0}": ("mlp", "forward"),
        # no op_name of its own: its body's ROOT's
        "%bare_fusion = f32[8,8]{1,0}": ("mlp", "forward"),
    }


def test_operation_key_is_the_line_up_to_the_opcode():
    # as a device trace prints it: operand shapes, no metadata
    assert operation_key(
        "%fusion.7 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %x.1), kind=kLoop, "
        "calls=%fused_computation") == "%fusion.7 = f32[8,8]{1,0}"
    assert operation_key(
        "  ROOT %t = (bf16[2,4]{1,0:T(8,128)(2,1)}, f32[2]{0}) custom-call("
        "bf16[2,4]{1,0} %a)") == "%t = (bf16[2,4]{1,0:T(8,128)(2,1)}, f32[2]{0})"


# ---- (d) the engine's program.parts spans -------------------------------
def test_traced_engine_records_one_span_per_compiled_program():
    eng = _traced_engine("gpt")
    _serve(eng)
    spans = eng.tracer.spans(name="program.parts")
    assert sorted(s.attrs["program"] for s in spans) == \
        ["jit_decode", "jit_prefill"]
    assert eng.core.trace_counts == {"prefill": 1, "decode": 1, "verify": 0}
    for s in spans:
        assert s.lane == eng.metrics.engine_lane and s.start == s.end
        table = s.attrs["parts"]
        named = {part for part, _ in table.values()}
        assert {"attention", "mlp", "head", "sampling"} <= named
        assert all(key.startswith("%") and " = " in key for key in table)
    # a second request at the same width compiles nothing: no new span
    _serve(eng)
    assert len(eng.tracer.spans(name="program.parts")) == 2
    # another width is another compile under the same name
    rid = eng.submit(np.arange(1, 30, dtype=np.int32), max_new_tokens=2)
    while eng.step():
        pass
    assert eng.result(rid).status == "finished"
    programs = [s.attrs["program"]
                for s in eng.tracer.spans(name="program.parts")]
    assert sorted(programs) == ["jit_decode", "jit_prefill", "jit_prefill"]
    # a window that starts after warm-up still finds every table
    eng.metrics.reset()
    again = eng.tracer.spans(name="program.parts")
    assert sorted(s.attrs["program"] for s in again) == sorted(programs)
    assert len(eng.tracer.spans()) == 3
    eng.close()


def test_untraced_engine_records_no_parts():
    model, kw = FAMILIES["gpt"]()
    eng = ServingEngine(model, num_slots=3, min_bucket=8, **kw)
    _serve(eng)
    assert eng.tracer.spans(name="program.parts") == []
    # it holds the programs themselves
    assert isinstance(eng.core._prefill_fn, functools.partial)
    assert isinstance(eng.core._decode_fn, functools.partial)
    eng.close()


# ---- (e) the scopes add no work -----------------------------------------
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scopes_add_no_equation(family, programs, monkeypatch):
    with_scopes = programs(family)["decode"]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _program_jaxprs(family)["decode"]
    assert not any(parts.parts_on_path(path)
                   for _, path in _matmul_paths(without.jaxpr))
    assert without.pretty_print(source_info=False, name_stack=False) == \
        with_scopes.pretty_print(source_info=False, name_stack=False)
