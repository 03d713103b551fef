"""What PR 34 adds to the benchmark, in files of its own (a PR adds to
the benchmark and edits nothing it has): the DeepSeek-V3-shaped family's
counts (``builders/deepseek_v3.py``: stored parameters, the dense
weights a step always reads, one expert's, the latent row's bytes), the
configuration file against the catalog row it was copied from, the
traffic file against the issue, ``lib/moe_flops_bytes.py`` against
hand-worked numbers, the three roofline readers on hand-made runs (a
share over 100% is an error there), and the new cell's driver path,
chunked prefill into a latent cache through dropless experts, end to
end on the CPU through a rehearsal manifest of its own
(``tests/benchmarks/rehearsal_moe/``)."""

import json
import os

import jax
import pytest

from benchmarks import run as bench_run
from benchmarks.builders import deepseek_v3
from benchmarks.lib import flops_bytes, moe_flops_bytes, peaks
from benchmarks.lib.peaks import CHIP_PEAKS

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_moe")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "deepseek-tiny.serve"
REAL_CELL = "joyai-llm-flash.serve-assist-4k"
SEED = 2 ** 31 + 34         # the driver's seeds pass 32 signed bits
TPU = peaks.chip_peaks("TPU v5 lite")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real_facts():
    return deepseek_v3.facts(
        load(ROOT, "benchmarks", "configs", "joyai-llm-flash-d5.json"))


# ------------------------------------------------------------ the counts

def test_facts_at_the_published_sizes(real_facts):
    facts = real_facts
    attn = 3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608
    assert attn + 2_048 == 26_347_520                   # ISSUE 34
    assert facts["expert_params"] == 4_718_592
    assert facts["stored_params"] == 5_558_141_952
    assert facts["stored_params"] == 529_530_880 + 70_391_808 \
        + 4 * 1_239_554_304 + 2_048
    # what a step reads whatever the routing: 5 attentions, the dense
    # MLP, 4 shared experts, 4 routers, the head
    assert facts["dense_params"] == 5 * attn + 44_040_192 \
        + 4 * (4_718_592 + 524_288) + 264_765_440
    assert facts["dense_params"] * 2 == pytest.approx(0.92e9, rel=0.01)
    assert (facts["expert_layers"], facts["experts"],
            facts["experts_per_token"]) == (4, 256, 8)
    assert facts["head_params"] == facts["lookup_params"] == 129280 * 2048
    # a cached position: 5 layers x one 576-wide row, held in 640 lanes
    assert (facts["latent_row_width"], facts["cache_row_width"]) \
        == (576, 640)
    assert moe_flops_bytes.latent_row_bytes(facts) == 6_400
    assert flops_bytes.kv_row_bytes(facts) == 6_400     # the detail line
    assert facts["expert_matmul_ops"] == ("gmm",)


def test_facts_count_the_models_real_parameters():
    from benchmarks.lib.build import build_model
    from paddle_tpu.nn.functional_call import state
    from paddle_tpu.serving.kv_pool import KVPool
    cfg = load(REHEARSAL, "configs", "deepseek-tiny.json")
    model, mcfg = build_model(deepseek_v3, cfg, seed=3)
    real = sum(v.size for v in state(model)[0].values())
    facts = deepseek_v3.facts(cfg)
    assert facts["stored_params"] == real == mcfg.num_params()
    assert (facts["expert_layers"], facts["experts"]) \
        == model.expert_routing_spec() == (2, 8)
    pool = KVPool.create(model, 2, 32)
    assert pool.row_bytes == moe_flops_bytes.latent_row_bytes(
        {**facts, "dtype": "float32"}) == 3 * 128 * 4


def test_the_configuration_file_holds_the_catalog_row():
    """Every key of the catalog's ``config``, booleans and nulls too,
    under the same key with the same value, but the two reduced; each
    assumption a sentence."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    manifest = load(ROOT, "BENCHMARK.json")
    entry, = [c for c in manifest["configs"]
              if c["name"] == "joyai-llm-flash-d5"]
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, filter(str.strip, f))
                if r["source_url"] == entry["source"]]
    body = load(ROOT, entry["file"])
    reduced = {"num_hidden_layers": (40, 5),
               "num_nextn_predict_layers": (1, 0)}
    for key, value in row["config"].items():
        if key in reduced:
            assert (value, body[key]) == reduced[key], key
            assert (body["reduced"][key]["published"],
                    body["reduced"][key]["run"]) == reduced[key]
        else:
            assert key in body and body[key] == value, key
    assert entry["reduced"] == sorted(reduced) == sorted(body["reduced"])
    # every width, every expert, the whole vocabulary
    assert (body["n_routed_experts"], body["num_experts_per_tok"],
            body["vocab_size"], body["moe_intermediate_size"]) \
        == (256, 8, 129280, 768)
    assert body["torch_dtype"] == "bfloat16"
    assert body["builder"] == "deepseek_v3"
    for topic in ("torch_dtype", "float32_in_the_program", "initializer",
                  "rotary_pairing", "attention", "routing", "layer",
                  "max_position_embeddings"):
        assert len(body["assumed"][topic]) > 40, topic
    assert "pipeline stage of 5 layers with every expert" \
        in body["deployment"]
    engine = body["engine"]
    assert (engine["num_slots"], engine["max_seq"],
            engine["enable_prefix_cache"]) == (32, 4096, False)
    assert engine["prefill_chunk"] in (512, 1024, 2048)
    assert len(body["engine_why"]) > 200


def test_the_traffic_file_holds_the_issues_parameters():
    manifest = load(ROOT, "BENCHMARK.json")
    cell, = [w for w in manifest["workloads"] if w["name"] == REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("joyai-llm-flash-d5", "serve-assist-4k", 1)
    assert len(cell["why"]) <= 200
    mix = load(ROOT, "benchmarks", "traffic", "serve-assist-4k.json")
    assert mix["kind"] == "open_loop"
    assert mix["prompt_len"] == {"median": 768, "sigma": 0.7, "min": 64,
                                 "max": 3072}
    assert mix["output_len"] == {"median": 192, "sigma": 0.5, "min": 32,
                                 "max": 768}
    # prompt + output at most 3840 of the 4096 rows
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 3840
    assert (mix["reference_samples"], mix["drain_seconds"],
            mix["trace_start_share"], mix["trace_seconds"],
            mix["decode_module_prefix"]) == (4, 30.0, 0.5, 3.0, "jit_decode")
    assert mix["rate_per_s"] > 0 and "sweep" in mix["rate_source"] \
        and "0.8" in mix["rate_source"]
    for section, names in (
            ("end_to_end", {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}),
            ("per_layer", {"ttft_p95_ms", "queue_wait_p95_ms",
                           "batch_occupancy", "decode_step_ms",
                           "device_idle.serve", "step_host_ms",
                           "prefill_stall_ms", "readback_return_ms",
                           "prefill_chunk_ms", "moe_decode_roofline_share",
                           "moe_prefill_roofline_share",
                           "expert_matmul_roofline_share"})):
        got = {m["name"] for m in manifest[section]
               if "workloads" not in m or REAL_CELL in m["workloads"]}
        assert got == names, section
    # the readers whose arithmetic is wrong for sparse experts, and the
    # looped model's, do not list the cell
    for name in ("decode_roofline_share", "prefill_roofline_share",
                 "loop_pass_ms"):
        entry, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert REAL_CELL not in entry["workloads"]


# ------------------------------------- a step's and a chunk's least time

def test_a_decode_steps_flops_and_bytes(real_facts):
    facts = real_facts
    # ISSUE 34: 32 live slots touch about 163 of 256 experts a layer:
    # dense 0.92 GB + 652 experts x 9,437,184 B = 7.07 GB, 8.6 ms
    weights = moe_flops_bytes.decode_step_bytes(facts, 32, 0, 4 * 163) \
        - 32 * 6_400
    assert weights == 2 * facts["dense_params"] + 652 * 9_437_184
    assert weights == pytest.approx(7.07e9, rel=0.005)
    assert weights / 819e9 == pytest.approx(8.6e-3, rel=0.01)
    rows = 32 * 1000
    byts = moe_flops_bytes.decode_step_bytes(facts, 32, rows, 652)
    assert byts == weights + (rows + 32) * 6_400
    flops = moe_flops_bytes.decode_step_flops(facts, 32, rows)
    # 2 x (dense + 8 experts in each of 4 layers) a slot, and the
    # absorbed attention's 2 x 32 x (576 + 512) a live row a layer
    assert flops == 2 * (facts["dense_params"] + 32 * 4_718_592) * 32 \
        + 5 * 2 * 32 * (576 + 512) * rows
    t, bound = flops_bytes.least_time_s(flops, byts, TPU)
    assert bound == "hbm" and t == pytest.approx(byts / 819e9)
    assert moe_flops_bytes.experts_cap(facts, 8) == 256
    assert moe_flops_bytes.experts_cap(facts, 32) == 1024
    assert moe_flops_bytes.experts_cap(facts, 100) == 1024
    # one live slot: 8 experts a layer, 0.30 GB of experts
    assert moe_flops_bytes.touched_expert_bytes(facts, 32) == 32 * 9_437_184


def test_a_prefill_chunks_flops_and_bytes(real_facts):
    facts = real_facts
    byts = moe_flops_bytes.chunk_bytes(facts, 512, 1024, 1000)
    assert byts == 2 * facts["dense_params"] + 1000 * 9_437_184 \
        + 1536 * 6_400
    flops = moe_flops_bytes.chunk_flops(facts, 512, 1024)
    body = facts["dense_params"] - facts["head_params"] + 32 * 4_718_592
    # the 512-wide chunk's 0.36 TFLOP of matrix work (ISSUE 34), ONE row
    # of logits, and the ABSORBED attention the program runs: 576-wide
    # scores and 512-wide sums per head per (query, seen row) pair
    assert 2 * body * 512 == pytest.approx(0.356e12, rel=0.01)
    assert flops == 2 * body * 512 + 2 * facts["head_params"] \
        + 5 * 2 * 32 * (576 + 512) * (512 * 1024 + 512 * 513 / 2)
    t, bound = flops_bytes.least_time_s(flops, byts, TPU)
    assert bound == "hbm" and 0.012 < t < 0.013


def test_a_share_over_the_roofline_is_an_error():
    assert moe_flops_bytes.share(0.008, 0.010, "x") == pytest.approx(80.0)
    with pytest.raises(ValueError, match="a count is too high"):
        moe_flops_bytes.share(0.0101, 0.010, "x")


# ----------------------------------------------------- the three readers

@pytest.fixture(scope="module")
def readers():
    files = bench_run.Files(os.path.join(ROOT, "BENCHMARK.json"))
    return tuple(files.module(f"metrics/{name}.py").read for name in (
        "moe_decode_roofline_share", "moe_prefill_roofline_share",
        "expert_matmul_roofline_share"))


def traced_run(facts, steps=(), chunks=(), programs=(), ops=(),
               clock=(10.0, 13.0)):
    """``serving.step`` spans ``(active, live rows, experts touched)``
    and ``prefill_chunk`` spans ``(chunk, width, tokens, touched)`` as
    the serving driver hands them over; ``programs`` / ``ops`` ``(name,
    start ns, milliseconds)`` on device 0's module and op lines."""
    spans = [("serving.step", 10.0 + 0.01 * k, 10.009 + 0.01 * k,
              {"active_slots": a, "live_kv_rows": r,
               **({} if t is None else {"experts_touched": t})})
             for k, (a, r, t) in enumerate(steps)]
    spans += [("prefill_chunk", 11.0 + 0.1 * k, 11.05 + 0.1 * k,
               {"chunk": c, "width": w, "tokens": n, "offset": c * 512,
                **({} if t is None else {"experts_touched": t})})
              for k, (c, w, n, t) in enumerate(chunks)]
    log = []

    def events(rows):
        return [(name, start, int(ms * 1e6)) for name, start, ms in rows]
    return {"spans": spans, "log": log.append, "lines": log,
            "trace_clock": clock, "facts": facts, "peaks": TPU,
            "trace_window_ns": (0, 10 ** 10),
            "decode_module_prefix": "jit_decode",
            "trace": {"host": [], "devices": {0: {
                "ops": events(ops), "modules": events(programs)}}}}


def test_moe_decode_share_counts_the_experts_touched(readers, real_facts):
    decode, _, _ = readers
    steps = [(32, 32_000, 652), (8, 8_000, 229)]
    run = traced_run(real_facts, steps=steps, programs=[
        ("jit_decode(1)", 0, 12.0), ("jit_decode(1)", 10 ** 8, 8.0),
        ("jit_prefill(2)", 2 * 10 ** 8, 25.0)])
    least = [moe_flops_bytes.decode_step_bytes(real_facts, a, r, t) / 819e9
             for a, r, t in steps]
    assert decode(run) == pytest.approx(100 * sum(least) / 2 / 0.010)
    assert 60 < decode(run) < 70
    assert "mean experts touched 440.5 of at most 640.0" in run["lines"][-1]
    assert "bound by {'hbm': 2}" in run["lines"][-1]
    # parked rows routed: more experts than the live slots can reach
    with pytest.raises(ValueError, match="parked rows were routed"):
        decode(traced_run(real_facts, steps=[(8, 8_000, 300)],
                          programs=[("jit_decode(1)", 0, 12.0)]))
    # the device finished sooner than the bytes allow: a count is wrong
    with pytest.raises(ValueError, match="a count is too high"):
        decode(traced_run(real_facts, steps=[(32, 32_000, 652)],
                          programs=[("jit_decode(1)", 0, 5.0)]))


def test_moe_decode_share_also_prices_the_windows_own_steps(readers,
                                                            real_facts):
    """The traced slice runs fuller than the window (the profiler's
    start backs the queue up): a second earlier line prices the WINDOW's
    decode-only steps at their own counts against their host time."""
    decode, _, _ = readers
    run = traced_run(real_facts, steps=[(32, 32_000, 652)],
                     programs=[("jit_decode(1)", 0, 10.0)])
    # the window: three steps (7, 8, 9), the second one prefilled
    counts = {7: (12, 12_000, 310), 8: (13, 13_000, 330),
              9: (14, 14_000, 350)}
    for i, (a, r, t) in counts.items():
        run["spans"] += [
            ("serving.step", i, i + 0.0075,
             {"step": i, "active_slots": a, "live_kv_rows": r,
              "experts_touched": t}),
            ("step.decode_dispatch", i, i + 0.001, {"step": i}),
            ("step.readback", i + 0.001, i + 0.007, {"step": i})]
    run["steps"] = [(0, 0, 12, 12_000, 0), (0, 0, 13, 13_000, 512),
                    (0, 0, 14, 14_000, 0)]
    assert 80 < decode(run) < 90
    least = [moe_flops_bytes.decode_step_bytes(real_facts, *counts[i])
             / 819e9 for i in (7, 9)]
    line = run["lines"][-1]
    assert "the WINDOW's 2 decode-only steps" in line
    assert "median active 13.0, mean experts touched 330.0" in line
    assert f"= {100 * sum(least) / 2 / 0.007:.1f}%" in line
    # a run without the window's rows (an older driver): one line only
    del run["steps"]
    decode(run)
    assert "WINDOW" not in run["lines"][-1]


def test_moe_prefill_share_prices_every_chunk_of_the_slice(readers,
                                                           real_facts):
    _, prefill, _ = readers
    chunks = [(0, 512, 512, 950), (1, 512, 512, 960), (2, 128, 100, 700)]
    run = traced_run(real_facts, chunks=chunks, programs=[
        ("jit_prefill(3)", 0, 23.0), ("jit_prefill(3)", 10 ** 8, 23.4),
        ("jit_prefill(5)", 2 * 10 ** 8, 16.0),
        ("jit_decode(7)", 3 * 10 ** 8, 9.0)])
    least = [flops_bytes.least_time_s(
        moe_flops_bytes.chunk_flops(real_facts, w, c * 512),
        moe_flops_bytes.chunk_bytes(real_facts, w, c * 512, t), TPU)[0]
        for c, w, _, t in chunks]
    assert prefill(run) == pytest.approx(
        100 * sum(least) / 3 / (0.0624 / 3))
    assert 50 < prefill(run) < 60
    assert "3 chunks by width {128: 1, 512: 2}" in run["lines"][-1]
    with pytest.raises(ValueError, match="padding was routed"):
        prefill(traced_run(real_facts, chunks=[(0, 16, 5, 200)],
                           programs=[("jit_prefill(3)", 0, 23.0)]))


def test_expert_matmul_share_reads_the_kernels_own_time(readers,
                                                        real_facts):
    _, _, kernel = readers
    name = "%gmm.2 = f32[256,2048]{1,0} custom-call(...)"
    run = traced_run(
        real_facts, steps=[(32, 32_000, 652), (32, 32_000, 660)],
        programs=[("jit_decode(1)", 0, 12.0),
                  ("jit_decode(1)", 10 ** 8, 12.0),
                  ("jit_prefill(2)", 2 * 10 ** 8, 25.0)],
        ops=[(name, 10 ** 6, 4.0), (name, 5 * 10 ** 6, 4.2),
             ("%fusion.1 = bf16[32,129280] fusion(...)", 10 ** 7, 0.7),
             (name, 10 ** 8 + 10 ** 6, 8.2),
             # a grouped matmul of a PREFILL program is not a decode's
             (name, 2 * 10 ** 8 + 10 ** 6, 12.0)])
    least = 656 * 9_437_184 / 819e9
    assert kernel(run) == pytest.approx(100 * least / 0.0082)
    assert 90 < kernel(run) < 95
    assert "over 2 programs" in run["lines"][-1]


def test_the_readers_give_nothing_without_their_input(readers, real_facts):
    steps, chunks = [(32, 32_000, 652)], [(0, 512, 512, 950)]
    programs = [("jit_decode(1)", 0, 12.0), ("jit_prefill(3)", 10 ** 8, 23.0)]
    ops = [("%gmm.2 = f32[256,2048] custom-call(...)", 10 ** 6, 8.0)]
    full = dict(steps=steps, chunks=chunks, programs=programs, ops=ops)
    for read in readers:
        assert read(traced_run(real_facts, **full)) is not None
        assert read({"spans": None, "facts": real_facts}) is None  # untraced
        # the CPU rehearsal: spans, no device plane
        assert read({**traced_run(real_facts, **full),
                     "trace": {"host": [], "devices": {}}}) is None
        # a program older than the counters (the parent): no count
        assert read(traced_run(
            real_facts, steps=[(32, 32_000, None)],
            chunks=[(0, 512, 512, None)], programs=programs,
            ops=ops)) is None
        # a family without expert layers
        assert read(traced_run({"layers": 2, "dtype": "bfloat16"},
                               **full)) is None
        assert read({**traced_run(real_facts, **full),
                     "trace_clock": None}) is None
    decode, prefill, kernel = readers
    assert decode(traced_run(real_facts, steps=steps, programs=[
        ("jit_prefill(3)", 0, 23.0)])) is None
    assert prefill(traced_run(real_facts, chunks=chunks, programs=[
        ("jit_decode(1)", 0, 12.0)])) is None
    # the trace names no grouped matmul (another form won): nothing
    assert kernel(traced_run(real_facts, steps=steps, programs=programs,
                             ops=[("%fusion.9 = ...", 10 ** 6, 8.0)])) is None


# ------------------------------------------- the cell's driver path, on CPU

@pytest.fixture(scope="module")
def files():
    return bench_run.Files(os.path.join(REHEARSAL, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def lines(files, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("out"))
    return {trace: json.loads(json.dumps(bench_run.run_cell(
        files, CELL, SEED, 2.0, trace, jax.devices()[:1],
        CHIP_PEAKS["TPU v5 lite"], out))) for trace in (False, True)}


@pytest.mark.parametrize("trace", [False, True])
def test_moe_cell_is_correct_on_the_cpu(lines, trace):
    """Chunked prefill into latent rows, ragged decode through dropless
    experts, and the sampled requests' tokens against the float32
    reference."""
    line = lines[trace]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("trace", [False, True])
def test_moe_cell_reports_the_manifests_metrics(files, lines, trace):
    """The three new readers are listed, found by name and run; a CPU
    trace has no device plane, so they return nothing and are left out
    of the line, never invented."""
    section = "per_layer" if trace else "end_to_end"
    listed = files.metrics_of(section, CELL)
    if trace:
        assert {"moe_decode_roofline_share", "moe_prefill_roofline_share",
                "expert_matmul_roofline_share"} \
            <= {m["name"] for m in listed}
    wanted = {m["name"]: m["unit"] for m in listed
              if m["source"] != "device_trace"}
    got = {k: v["unit"] for k, v in lines[trace]["metrics"].items()}
    assert got == wanted
    assert all(v["value"] > 0 for v in lines[trace]["metrics"].values())


@pytest.mark.parametrize("fault", ["shared_expert_left_out",
                                   "mlp_inputs_to_4_bits",
                                   "matmul_inputs_to_4_bits",
                                   "routed_scale_left_out"])
def test_the_cells_comparison_fails_on_a_wrong_build(files, tmp_path,
                                                     monkeypatch, fault):
    """``scripts/wrong_builds.py``: the same run with ONE fault in the
    program reads ``correct: false``, by the tokens' check alone (the
    chip's readings at the cell's size: PERF.md section 6, PR 34)."""
    from scripts import wrong_builds
    wrong_builds.install(fault, monkeypatch.setattr)
    line = bench_run.run_cell(files, CELL, SEED, 2.0, False,
                              jax.devices()[:1],
                              CHIP_PEAKS["TPU v5 lite"], str(tmp_path))
    assert line["correct"] is False and line["failed"] == 0
    out = load(str(tmp_path), f"{CELL}.seed{SEED}.trace0.json")
    assert out["checks"] == {"all_due_finished": True,
                             "no_program_in_window": True,
                             "tokens_near_reference_argmax": False}


def test_four_bits_is_float8s_significand():
    """The wrong builds' rounding against the float8 e4m3 round trip,
    in the range where that format is normal, for both widths."""
    import jax.numpy as jnp
    import numpy as np
    from scripts.wrong_builds import four_bits
    rs = np.random.default_rng(0)
    x = rs.choice([-1.0, 1.0], 4096) * (1 + rs.random(4096)) \
        * np.exp2(rs.integers(-6, 8, 4096))
    ties = np.asarray([1.0625, 1.1875, -1.3125, 3.25, 0.0, -0.0])
    for dtype in (jnp.bfloat16, jnp.float32):
        v = jnp.asarray(np.concatenate([x, ties]), dtype)
        want = v.astype(jnp.float8_e4m3fn).astype(dtype)
        np.testing.assert_array_equal(np.asarray(four_bits(v), np.float32),
                                      np.asarray(want, np.float32))


def test_moe_cell_counts_experts_on_both_programs(files, tmp_path):
    """The driver path above ran chunks and decode steps whose spans
    carry the experts they touched, under what their live tokens can
    reach; the warm-up reached every width the window used."""
    from benchmarks.drivers import open_loop
    ctx, _ = bench_run.make_context(
        files, CELL, SEED, 1.0, True, jax.devices()[:1],
        CHIP_PEAKS["TPU v5 lite"], str(tmp_path))
    model, mcfg, eng, programs = open_loop.build(ctx)
    try:
        facts = deepseek_v3.facts(ctx.config)
        assert eng.core.prefill_chunk == 16
        assert eng.core.pool.row_kinds == 1
        chunks = [s.attrs for s in eng.tracer.spans()
                  if s.name == "prefill_chunk"]
        assert {a["width"] for a in chunks} == {8, 16}
        assert all(0 < a["experts_touched"]
                   <= moe_flops_bytes.experts_cap(facts, a["tokens"])
                   for a in chunks)
        steps = [s.attrs for s in eng.tracer.spans(lane=0)
                 if s.name == "serving.step" and s.attrs["active_slots"]]
        assert steps and all(
            0 < a["experts_touched"]
            <= moe_flops_bytes.experts_cap(facts, a["active_slots"])
            for a in steps)
        assert programs["prefill"] == 2 and programs["decode"] == 1
    finally:
        eng.close()
