"""Zero-cold-start contract gate (ISSUE 17; serving/aot.py).

The AOT program store turns the compile-surface manifest (ISSUE 16)
into a build input: ``scripts/aot_build.py build`` lowers every
manifest program on the ``EngineCore`` plane and an engine constructed
with ``aot_store=`` LOADS instead of traces.  This suite pins the
contract from both sides:

  * zero-compile warm load — a warm-loaded engine ticks ZERO trace
    counters across admit/prefill/decode/gather/scatter on every leg
    (tp=1 composed, tp=1 fused, tp=2) while staying token-identical
    (greedy AND seeded sampling) to a traced engine;
  * keying — a fingerprint mismatch degrades gracefully to tracing
    ("skew", the engine still serves), while bucket drift under a
    MATCHING fingerprint is a loud ``AOTStoreError`` (a store that
    agrees on the config but not the program set is a build bug);
  * durability — publish is atomic (a crashed build leaves NO index,
    so ``open`` refuses; torn tmp files are invisible) and refuses a
    store missing any manifest program id;
  * chaos — a corrupt artifact (real byte flip or the ``aot_load`` /
    ``aot_store_corrupt`` injection points) degrades that program to
    trace-on-demand with the accounting invariant and compile pin
    intact, never a crash;
  * fleet — an autoscaler spawn handed the shared store comes up warm
    (zero traces) and token-identical to its traced twin;
  * CLI — ``aot_build.py build`` then ``verify`` exits 0; ``verify``
    exits 1 the moment an artifact is missing; ``gc`` collects
    unreferenced objects.

zz-prefixed for the same reason as test_zz_compile_surface: the tp=2
leg drives shard_map on the 8-device CPU mesh and must sort after the
jaxlib-0.4 dispatch-race window conftest documents.
"""

import json
import math
import os
import shutil

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.obs import MetricsRegistry, Tracer
from paddle_tpu.serving import (AOTStore, AOTStoreError, Autoscaler,
                                FaultInjector, Router, SamplingParams,
                                ServingEngine, aot_fingerprint,
                                build_engine_store, engine_aot_context,
                                replica_accounting)
from paddle_tpu.serving.engine import EngineCore

ENGINE_KW = dict(num_slots=4, max_seq=64, min_bucket=8,
                 prefill_chunk=16, block_len=16)
# the static prefill bound for this shape: chunk program + pow2 tails
MAX_PREFILL = int(math.log2(ENGINE_KW["max_seq"]
                            // ENGINE_KW["min_bucket"])) + 2
LEGS = {
    "tp1": {},
    "tp1_fused": {"fused_decode": True},
    "tp2": {"tensor_parallel": 2},
}


def _fresh_gpt(seed=0):
    paddle_tpu.seed(seed)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def manifest():
    """ONE manifest for every build in this module (the same library
    entry point ``graftlint --manifest`` and the CLI use)."""
    from paddle_tpu.tools.analysis import build_manifest_for_paths
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    scope = [os.path.join(root, p)
             for p in ("paddle_tpu", "scripts")]
    return build_manifest_for_paths(scope, root=root)


@pytest.fixture(scope="module")
def stores(tmp_path_factory, manifest):
    """One published store per leg, built once for the module."""
    out = {}
    for leg, extra in LEGS.items():
        core = EngineCore(_fresh_gpt(), **ENGINE_KW, **extra)
        path = str(tmp_path_factory.mktemp(f"aot_{leg}"))
        build_engine_store(path, core, manifest=manifest)
        out[leg] = path
    return out


def _run(eng):
    """Mixed-length greedy prompts + two seeded sampled ones, then a
    resubmit so the prefix cache drives gather AND scatter; returns
    (tokens per request, observed trace counters)."""
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 256, (L,)) for L in (3, 9, 17, 50)]
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    rids.append(eng.submit(
        rs.randint(0, 256, (12,)), max_new_tokens=3,
        sampling=SamplingParams(do_sample=True, temperature=2.0,
                                seed=3)))
    rids.append(eng.submit(
        rs.randint(0, 256, (30,)), max_new_tokens=3,
        sampling=SamplingParams(do_sample=True, top_k=5, top_p=0.7,
                                seed=4)))
    eng.run_until_complete(800)
    rids.append(eng.submit(prompts[-1].copy(), max_new_tokens=3))
    eng.run_until_complete(200)
    outs = [eng.result(r) for r in rids]
    assert all(o.finished for o in outs)
    observed = dict(eng.core.trace_counts)
    observed.update(eng.core.block_pool.trace_counts)
    return [tuple(o.tokens) for o in outs], observed


def _counter(eng, name):
    inst = eng.metrics.registry.get(name)
    return 0 if inst is None else inst.value


# ------------------------------------------------- zero-compile legs

@pytest.mark.parametrize("leg", sorted(LEGS))
def test_warm_engine_compiles_nothing_and_matches_traced(leg, stores):
    """THE acceptance bar: a warm-loaded engine ticks zero trace
    counters across the full workload and is token-identical (greedy +
    seeded sampling) to a traced engine with the same weights."""
    traced_tokens, traced_obs = _run(
        ServingEngine(_fresh_gpt(), **ENGINE_KW, **LEGS[leg]))
    assert traced_obs["prefill"] > 0      # the cold leg really traced

    store = AOTStore.open(stores[leg])
    try:
        eng = ServingEngine(_fresh_gpt(), aot_store=store,
                            **ENGINE_KW, **LEGS[leg])
        assert eng.aot_status == "warm", eng.aot_status
        warm_tokens, warm_obs = _run(eng)
    finally:
        store.close()
    assert warm_obs == {"prefill": 0, "decode": 0, "verify": 0,
                        "gather": 0, "scatter": 0}, (
        f"[{leg}] warm engine traced: {warm_obs}")
    assert warm_tokens == traced_tokens, (
        f"[{leg}] warm tokens diverged from traced")
    assert _counter(eng, "aot.loads") == len(store.programs())
    assert _counter(eng, "aot.fallbacks") == 0
    acc = replica_accounting(eng)
    assert acc["ok"], acc


# ---------------------------------------------------- store contract

def test_store_roundtrip_and_close(stores):
    store = AOTStore.open(stores["tp1"])
    try:
        core = EngineCore(_fresh_gpt(), **ENGINE_KW)
        assert store.fingerprint == aot_fingerprint(
            engine_aot_context(core))
        assert store.widths == core.warm_buckets()
        names = set(store.programs())
        assert {f"prefill:w{w}" for w in store.widths} <= names
        assert "gather" in names and "scatter" in names
        assert any(n.startswith("decode:") for n in names)
        fn = store.load_call("gather")
        assert callable(fn)
        assert store.build_seconds > 0
    finally:
        store.close()
    with pytest.raises(AOTStoreError, match="closed"):
        store.load("gather")


def test_warm_buckets_enumeration():
    """The committed-width set is exact for this shape: chunk ladder
    union block-start ladder, pow2 capped at max_seq."""
    core = EngineCore(_fresh_gpt(), **ENGINE_KW)
    assert core.warm_buckets() == (8, 16, 32, 48, 64)


def test_fingerprint_mismatch_degrades_to_tracing(stores):
    """A config the store was not built for serves TRACED ("skew"),
    never crashes and never half-loads."""
    store = AOTStore.open(stores["tp1"])
    try:
        eng = ServingEngine(_fresh_gpt(), aot_store=store, num_slots=2,
                            **{k: v for k, v in ENGINE_KW.items()
                               if k != "num_slots"})
        assert eng.aot_status == "skew"
        tokens, observed = _run(eng)
        assert observed["prefill"] > 0 and observed["decode"] == 1
        assert _counter(eng, "aot.loads") == 0
        assert _counter(eng, "aot.misses") >= 1
    finally:
        store.close()


def test_bucket_drift_under_matching_fingerprint_is_loud(stores,
                                                         tmp_path):
    """Same fingerprint but a different committed-width set is a build
    bug, not an environment change — constructing the engine raises."""
    tampered = str(tmp_path / "tampered")
    shutil.copytree(stores["tp1"], tampered)
    idx_path = os.path.join(tampered, "index.json")
    with open(idx_path) as f:
        idx = json.load(f)
    idx["widths"] = idx["widths"][:2]
    with open(idx_path, "w") as f:
        json.dump(idx, f)
    store = AOTStore.open(tampered)
    try:
        with pytest.raises(AOTStoreError, match="widths"):
            ServingEngine(_fresh_gpt(), aot_store=store, **ENGINE_KW)
    finally:
        store.close()


# ------------------------------------------------- publish atomicity

def test_crashed_build_publishes_nothing(tmp_path, manifest):
    """A build that dies before publish leaves no index — readers
    refuse the directory outright (objects are garbage, not state),
    and a torn index tmp file is invisible."""
    plane = manifest["planes"]["paddle_tpu.serving.engine.EngineCore"]
    path = str(tmp_path / "crashed")
    writer = AOTStore.create(path, context={"cfg": 1}, plane=plane,
                             widths=(8,))
    try:

        class _Fake:
            def serialize(self):
                return b"not a real artifact"

        writer.add("gather", _Fake())
    finally:
        writer.discard()        # the crash: never published
    assert os.path.isdir(path)
    with open(os.path.join(path, "index.json.tmp"), "w") as f:
        f.write('{"torn": ')
    with pytest.raises(AOTStoreError, match="no published"):
        AOTStore.open(path)


def test_publish_refuses_incomplete_and_unbounded(tmp_path, manifest):
    plane = manifest["planes"]["paddle_tpu.serving.engine.EngineCore"]

    class _Fake:
        def serialize(self):
            return b"x"

    writer = AOTStore.create(str(tmp_path / "partial"),
                             context={"cfg": 1}, plane=plane,
                             widths=(8, 16))
    try:
        writer.add("gather", _Fake())
        with pytest.raises(AOTStoreError, match="prefill:w8"):
            writer.publish()
    finally:
        writer.discard()

    bad_plane = {"decode": {"key_space": "unbounded",
                            "programs": ["d"]}}
    writer = AOTStore.create(str(tmp_path / "unbounded"),
                             context={"cfg": 1}, plane=bad_plane,
                             widths=())
    try:
        with pytest.raises(AOTStoreError, match="UNBOUNDED"):
            writer.publish()
    finally:
        writer.discard()


# -------------------------------------------------------------- chaos

def _assert_degraded_but_serving(eng, traced_tokens):
    tokens, observed = _run(eng)
    assert tokens == traced_tokens      # degradation never skews tokens
    # compile pin intact: the fallback traces stay inside the static
    # bounds the manifest proves
    assert observed["prefill"] <= MAX_PREFILL
    assert observed["decode"] <= 1
    assert observed["gather"] <= 1 and observed["scatter"] <= 1
    assert _counter(eng, "aot.fallbacks") >= 1
    acc = replica_accounting(eng)
    assert acc["ok"], acc


def test_corrupt_artifact_degrades_to_trace_on_demand(stores,
                                                      tmp_path):
    """A real byte flip in one artifact: CRC catches it at warm load,
    THAT program falls back to tracing, everything else stays warm."""
    traced_tokens, _ = _run(ServingEngine(_fresh_gpt(), **ENGINE_KW))
    rotted = str(tmp_path / "rotted")
    shutil.copytree(stores["tp1"], rotted)
    with open(os.path.join(rotted, "index.json")) as f:
        idx = json.load(f)
    obj = idx["programs"]["prefill:w8"]["object"]
    obj_path = os.path.join(rotted, "objects", obj + ".aot")
    blob = bytearray(open(obj_path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(obj_path, "wb") as f:
        f.write(bytes(blob))

    store = AOTStore.open(rotted)
    try:
        eng = ServingEngine(_fresh_gpt(), aot_store=store, **ENGINE_KW)
        assert eng.aot_status == "partial"
        _assert_degraded_but_serving(eng, traced_tokens)
    finally:
        store.close()


def test_aot_load_fault_degrades_one_program(stores):
    traced_tokens, _ = _run(ServingEngine(_fresh_gpt(), **ENGINE_KW))
    store = AOTStore.open(stores["tp1"])
    inj = FaultInjector()
    inj.enable("aot_load", at=0)
    try:
        eng = ServingEngine(_fresh_gpt(), aot_store=store, faults=inj,
                            **ENGINE_KW)
        assert inj.fired["aot_load"] == 1
        assert eng.aot_status == "partial"
        _assert_degraded_but_serving(eng, traced_tokens)
    finally:
        inj.disable("aot_load")
        store.close()


def test_aot_store_corrupt_fault_degrades_one_program(stores):
    traced_tokens, _ = _run(ServingEngine(_fresh_gpt(), **ENGINE_KW))
    inj = FaultInjector()
    inj.enable("aot_store_corrupt", at=0)
    store = AOTStore.open(stores["tp1"], faults=inj)
    try:
        eng = ServingEngine(_fresh_gpt(), aot_store=store, **ENGINE_KW)
        assert inj.fired["aot_store_corrupt"] == 1
        assert eng.aot_status == "partial"
        _assert_degraded_but_serving(eng, traced_tokens)
    finally:
        inj.disable("aot_store_corrupt")
        store.close()


# -------------------------------------------------------------- fleet

def test_autoscaler_spawn_from_store_is_warm_and_token_identical(
        stores):
    """The instant-autoscaler contract: a spawn handed the shared
    store joins the rotation with ZERO traces and serves the exact
    tokens its traced twin would."""
    traced_tokens, _ = _run(ServingEngine(_fresh_gpt(), **ENGINE_KW))
    store = AOTStore.open(stores["tp1"])
    try:
        registry, tracer = MetricsRegistry(), Tracer()
        router = Router.build(_fresh_gpt, replicas=1, registry=registry,
                              tracer=tracer, aot_store=store,
                              **ENGINE_KW)
        assert router.replicas[0].engine.aot_status == "warm"
        received = []

        def spawn_fn(aot_store=None):
            received.append(aot_store)
            return ServingEngine(_fresh_gpt(), registry=registry,
                                 tracer=tracer, aot_store=aot_store,
                                 **ENGINE_KW)

        scaler = Autoscaler(router, spawn_fn, aot_store=store,
                            min_decode=1, max_decode=3,
                            scale_up_depth=2, hysteresis_steps=2,
                            cooldown_steps=3)
        idx = scaler.spawn()
        assert idx is not None and received == [store]
        eng = router.replicas[idx].engine
        assert eng.aot_status == "warm"
        tokens, observed = _run(eng)
        assert observed == {"prefill": 0, "decode": 0, "verify": 0,
                            "gather": 0, "scatter": 0}
        assert tokens == traced_tokens
        scaler.retire(idx)
    finally:
        store.close()


def test_autoscaler_zero_arg_spawn_fn_still_works(stores):
    store = AOTStore.open(stores["tp1"])
    try:
        registry, tracer = MetricsRegistry(), Tracer()
        router = Router.build(_fresh_gpt, replicas=1, registry=registry,
                              tracer=tracer, **ENGINE_KW)

        def spawn_fn():
            return ServingEngine(_fresh_gpt(), registry=registry,
                                 tracer=tracer, **ENGINE_KW)

        scaler = Autoscaler(router, spawn_fn, aot_store=store,
                            min_decode=1, max_decode=3,
                            scale_up_depth=2, hysteresis_steps=2,
                            cooldown_steps=3)
        assert not scaler._spawn_takes_store
        idx = scaler.spawn()
        assert idx is not None
        assert router.replicas[idx].engine.aot_status is None
        scaler.retire(idx)
    finally:
        store.close()


# ---------------------------------------------------------------- CLI

def test_aot_build_cli_roundtrip(tmp_path):
    """build -> verify 0; delete one artifact -> verify 1; gc removes
    unreferenced objects — the tier-1 CPU smoke for the CLI."""
    from scripts.aot_build import main

    path = str(tmp_path / "cli_store")
    assert main(["build", path]) == 0
    assert main(["verify", path]) == 0

    with open(os.path.join(path, "index.json")) as f:
        idx = json.load(f)
    obj = idx["programs"]["gather"]["object"]
    os.remove(os.path.join(path, "objects", obj + ".aot"))
    assert main(["verify", path]) == 1

    garbage = os.path.join(path, "objects", "0" * 64 + ".aot")
    with open(garbage, "wb") as f:
        f.write(b"leftover from a crashed build")
    assert main(["gc", path]) == 0
    assert not os.path.exists(garbage)


# ------------------------------------------- speculative decoding (18)

def _run_spec(eng):
    """The shared workload plus one cyclic prompt the n-gram tables can
    draft from, so the verify program actually dispatches."""
    tokens, _ = _run(eng)
    r = eng.submit(np.tile([5, 6, 7, 8], 8), max_new_tokens=8)
    eng.run_until_complete(200)
    out = eng.result(r)
    assert out.finished
    tokens.append(tuple(out.tokens))
    observed = dict(eng.core.trace_counts)
    observed.update(eng.core.block_pool.trace_counts)
    return tokens, observed


def test_warm_spec_engine_compiles_nothing_and_matches_traced(
        tmp_path_factory, manifest):
    """ISSUE 18: a store built with speculation on carries the verify
    leg; a warm spec engine ticks ZERO trace counters — verify included
    — while drafting (acceptance > 0) and staying token-identical to a
    traced spec engine."""
    kw = dict(ENGINE_KW, spec_k=3)
    core = EngineCore(_fresh_gpt(), **kw)
    assert core.spec_on
    path = str(tmp_path_factory.mktemp("aot_spec"))
    build_engine_store(path, core, manifest=manifest)

    traced_tokens, traced_obs = _run_spec(
        ServingEngine(_fresh_gpt(), **kw))
    assert traced_obs["verify"] == 1      # the cold leg really traced

    store = AOTStore.open(path)
    try:
        assert any(n.startswith("verify:") for n in store.programs())
        eng = ServingEngine(_fresh_gpt(), aot_store=store, **kw)
        assert eng.aot_status == "warm", eng.aot_status
        warm_tokens, warm_obs = _run_spec(eng)
    finally:
        store.close()
    assert warm_obs == {"prefill": 0, "decode": 0, "verify": 0,
                        "gather": 0, "scatter": 0}, (
        f"warm spec engine traced: {warm_obs}")
    assert warm_tokens == traced_tokens, (
        "warm spec tokens diverged from traced")
    snap = eng.metrics.snapshot()
    assert snap["spec_draft_tokens"] > 0
    assert _counter(eng, "aot.fallbacks") == 0
    acc = replica_accounting(eng)
    assert acc["ok"], acc


def test_specless_store_refuses_spec_engine(stores):
    """A store built WITHOUT speculation (spec_k=0 context) cannot warm
    a speculating engine — the fingerprint disagrees, so the engine
    serves traced ("skew") rather than half-loading a plane with no
    verify leg."""
    store = AOTStore.open(stores["tp1"])
    try:
        eng = ServingEngine(_fresh_gpt(), aot_store=store,
                            spec_k=3, **ENGINE_KW)
        assert eng.aot_status == "skew"
    finally:
        store.close()
