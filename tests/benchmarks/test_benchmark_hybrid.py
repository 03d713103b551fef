"""What PR 32 adds to the benchmark, in files of its own (a PR adds to
the benchmark and edits nothing it has): the hybrid family's counts
(``builders/jamba.py``: stored parameters, a cached row's bytes, a
slot's recurrent state), the configuration file against the catalog row
it was copied from, the ``prefill_chunk_ms`` and
``prefill_roofline_share`` readers on hand-made runs, and the new cell's
driver path, chunked prefill carrying the state, end to end on the CPU
through a rehearsal manifest of its own
(``tests/benchmarks/rehearsal_hybrid/``)."""

import json
import os

import jax
import pytest

from benchmarks import run as bench_run
from benchmarks.builders import jamba
from benchmarks.lib import flops_bytes, peaks, prefill_flops_bytes
from benchmarks.lib.peaks import CHIP_PEAKS

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_hybrid")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "jamba-tiny.serve"
REAL_CELL = "jamba2-3b.serve-docqa-4k"
SEED = 2 ** 31 + 31         # the driver's seeds pass 32 signed bits


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real_facts():
    return jamba.facts(load(ROOT, "benchmarks", "configs", "jamba2-3b.json"))


# ------------------------------------------------------------ the counts

def test_jamba_facts_at_the_published_sizes(real_facts):
    facts = real_facts
    mamba = 26_214_400 + 25_600 + 983_040 + 824_320 + 81_920 + 5_120 \
        + 13_107_200 + 192
    assert mamba == 41_241_792                      # ISSUE 32's mixer
    assert facts["stored_params"] == 3_029_337_472
    assert facts["stored_params"] == 26 * (mamba + 62_914_560 + 5_120) \
        + 2 * 76_682_240 + 167_772_160 + 2_560
    assert facts["head_params"] == 65536 * 2560 and facts["lookup_params"] == 0
    # what multiplies activations in a matrix product: not the
    # convolution, A_log, D, the step's bias or the norms
    assert facts["matmul_params"] == facts["stored_params"] - 26 * (
        25_600 + 5_120 + 81_920 + 5_120 + 192 + 5_120) - 2 * 5_120 - 2_560
    assert (facts["layers"], facts["kv_heads"], facts["heads"],
            facts["head_dim"]) == (2, 1, 20, 128)
    # a cached row: 2 attention layers x (K, V) x 1 x 128 x 2 B = 1 KiB
    assert flops_bytes.kv_row_bytes(facts) == 1024
    # a slot: 26 x (5120 x 16 x 4 B + 5120 x 3 x 2 B)
    assert facts["state_bytes_per_slot"] == 9_318_400


def test_jamba_facts_count_the_models_real_parameters():
    from benchmarks.lib.build import build_model
    from paddle_tpu.nn.functional_call import state
    from paddle_tpu.serving.kv_pool import (recurrent_state_spec,
                                            state_bytes)
    cfg = load(REHEARSAL, "configs", "jamba-tiny.json")
    model, mcfg = build_model(jamba, cfg, seed=3)
    real = sum(v.size for v in state(model)[0].values())
    facts = jamba.facts(cfg)
    assert facts["stored_params"] == real == mcfg.num_params()
    assert facts["layers"] == mcfg.num_cache_layers == 2
    assert facts["state_bytes_per_slot"] \
        == state_bytes(recurrent_state_spec(model))


def test_the_configuration_file_holds_the_catalog_row():
    """Every key of the catalog's ``config``, booleans and nulls too,
    under the same key with the same value; nothing reduced; each
    assumption a sentence."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    manifest = load(ROOT, "BENCHMARK.json")
    entry, = [c for c in manifest["configs"] if c["name"] == "jamba2-3b"]
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, filter(str.strip, f))
                if r["source_url"] == entry["source"]]
    body = load(ROOT, entry["file"])
    for key, value in row["config"].items():
        assert key in body and body[key] == value, key
    assert entry["reduced"] == [] and body["reduced"] == {}
    assert body["torch_dtype"] == "bfloat16" and body["builder"] == "jamba"
    for topic in ("layer_order", "layer", "head_dim", "positional_encoding",
                  "projection_bias", "mamba_mixer", "recurrence_precision",
                  "initializer"):
        assert len(body["assumed"][topic]) > 40, topic
    assert body["engine"] == {
        "num_slots": 16, "max_seq": 4096, "prefill_chunk": 512,
        "max_prefills_per_step": 2, "enable_prefix_cache": False}


def test_the_traffic_file_holds_the_issues_parameters():
    manifest = load(ROOT, "BENCHMARK.json")
    cell, = [w for w in manifest["workloads"] if w["name"] == REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("jamba2-3b", "serve-docqa-4k", 1)
    mix = load(ROOT, "benchmarks", "traffic", "serve-docqa-4k.json")
    assert mix["kind"] == "open_loop"
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.5, "min": 512,
                                 "max": 3840}
    assert mix["output_len"] == {"median": 96, "sigma": 0.5, "min": 16,
                                 "max": 256}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 4096
    assert (mix["reference_samples"], mix["drain_seconds"],
            mix["trace_start_share"], mix["trace_seconds"],
            mix["decode_module_prefix"]) == (4, 30.0, 0.5, 3.0, "jit_decode")
    assert mix["rate_per_s"] > 0 and "sweep" in mix["rate_source"]
    for section, names in (
            ("end_to_end", {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}),
            ("per_layer", {"ttft_p95_ms", "queue_wait_p95_ms",
                           "batch_occupancy", "decode_step_ms",
                           "decode_roofline_share", "device_idle.serve",
                           "step_host_ms", "prefill_stall_ms",
                           "readback_return_ms", "prefill_chunk_ms",
                           "prefill_roofline_share"})):
        got = {m["name"] for m in manifest[section]
               if "workloads" not in m or REAL_CELL in m["workloads"]}
        assert got == names, section


# ------------------------------------------------- a chunk's least time

def test_a_prefill_chunks_flops_and_bytes(real_facts):
    facts = real_facts
    body = facts["matmul_params"] - facts["head_params"]
    assert body == 2_858_352_640
    flops = prefill_flops_bytes.chunk_flops(facts, 512, 1024)
    # ISSUE 32: 2 x 2.86 G x 512 = 2.93 TFLOP; one row of logits; two
    # attention layers' scores and sums over 1024 held rows + the chunk
    attn = 4 * 2 * 2560 * (512 * 1024 + 512 * 513 // 2)
    assert flops == 2 * body * 512 + 2 * 65536 * 2560 + attn
    assert flops == pytest.approx(2.93e12, rel=0.01)
    byts = prefill_flops_bytes.chunk_bytes(facts, 512, 1024)
    assert byts == 2 * facts["matmul_params"] + 2 * 9_318_400 + 1536 * 1024
    t, bound = flops_bytes.least_time_s(
        flops, byts, peaks.chip_peaks("TPU v5 lite"))
    assert bound == "flops" and t == pytest.approx(0.0149, rel=0.01)
    # a builder that says nothing of a head or a state: zeros, no raise
    plain = {k: v for k, v in facts.items()
             if k not in ("head_params", "state_bytes_per_slot")}
    assert prefill_flops_bytes.chunk_flops(plain, 512, 0) \
        == 2 * facts["matmul_params"] * 512 + 4 * 2 * 2560 * 512 * 513 / 2
    assert prefill_flops_bytes.chunk_bytes(plain, 512, 0) \
        == 2 * facts["matmul_params"] + 512 * 1024


# ------------------------------------------------------ the two readers

@pytest.fixture(scope="module")
def readers():
    files = bench_run.Files(os.path.join(ROOT, "BENCHMARK.json"))
    return (files.module("metrics/prefill_chunk_ms.py").read,
            files.module("metrics/prefill_roofline_share.py").read)


def prefill_run(chunks, programs, facts, clock=(10.0, 13.0)):
    """``prefill_chunk`` spans ``(chunk index, width, carried)`` as the
    serving driver hands them over, ``programs`` ``(name, start ns,
    milliseconds)`` on the device's module line."""
    spans = [("prefill_chunk", 10.0 + 0.1 * k, 10.05 + 0.1 * k,
              {"chunk": c, "width": w, "tokens": w, "request": 1,
               **({} if carried is None else {"state_carried": carried})})
             for k, (c, w, carried) in enumerate(chunks)]
    log = []
    return {"spans": spans, "window": (10.0, 20.0), "log": log.append,
            "lines": log, "trace_clock": clock, "facts": facts,
            "peaks": peaks.chip_peaks("TPU v5 lite"),
            "trace_window_ns": (0, 10 ** 10),
            "trace": {"host": [], "devices": {0: {"ops": [], "modules": [
                (name, start, int(ms * 1e6))
                for name, start, ms in programs]}}}}


def test_prefill_chunk_ms_is_the_median_prefill_program(readers, real_facts):
    chunk_ms, _ = readers
    run = prefill_run(
        [(0, 512, False), (1, 512, True), (2, 512, True), (3, 128, True)],
        [("jit_prefill(3)", 0, 30.0), ("jit_prefill(3)", 10 ** 8, 32.0),
         ("jit_prefill(3)", 2 * 10 ** 8, 31.0),
         ("jit_prefill(5)", 3 * 10 ** 8, 9.0),
         ("jit_decode(7)", 4 * 10 ** 8, 8.0)], real_facts)
    assert chunk_ms(run) == pytest.approx(30.5)
    line, = run["lines"]
    assert "4 programs" in line and "{128: 1, 512: 3}" in line
    assert "3 of 4 from a carried state" in line


def test_prefill_roofline_share_is_a_full_chunks_least_time(readers,
                                                            real_facts):
    _, share = readers
    run = prefill_run(
        [(0, 512, False), (2, 512, True), (3, 64, True)],
        [("jit_prefill(3)", 0, 30.0), ("jit_prefill(3)", 10 ** 8, 32.0),
         ("jit_prefill(5)", 2 * 10 ** 8, 31.0)], real_facts)
    tpu = peaks.chip_peaks("TPU v5 lite")
    least = [flops_bytes.least_time_s(
        prefill_flops_bytes.chunk_flops(real_facts, 512, rows),
        prefill_flops_bytes.chunk_bytes(real_facts, 512, rows), tpu)[0]
        for rows in (0, 1024)]
    assert share(run) == pytest.approx(100 * sum(least) / 2 / 0.031)
    assert 45 < share(run) < 50
    assert "2 of 3 chunks at width 512, bound by {'flops': 2}" \
        in run["lines"][-1]


def test_the_readers_give_nothing_without_their_input(readers, real_facts):
    programs = [("jit_prefill(3)", 0, 30.0)]
    chunks = [(0, 512, False)]
    for read in readers:
        assert read({"spans": None}) is None                  # untraced
        # the CPU rehearsal: spans, no device plane
        assert read({**prefill_run(chunks, programs, real_facts),
                     "trace": {"host": [], "devices": {}}}) is None
        # a slice that holds no prefill program
        assert read(prefill_run(chunks, [("jit_decode(7)", 0, 8.0)],
                                real_facts)) is None
    chunk_ms, share = readers
    # the parent's spans carry no state_carried: the count reads 0
    run = prefill_run([(0, 512, None)], programs, real_facts)
    assert chunk_ms(run) == pytest.approx(30.0)
    assert "0 of 1 from a carried state" in run["lines"][0]
    # programs but no chunk span in the slice: no width to price
    assert share(prefill_run([], programs, real_facts)) is None
    assert share({**prefill_run(chunks, programs, real_facts),
                  "trace_clock": None}) is None


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
def test_hybrid_cell_is_correct_on_the_cpu(lines, trace):
    """Chunked prefill carrying the state, ragged decode, and the
    sampled requests' tokens against the float32 reference."""
    line = lines[trace]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("trace", [False, True])
def test_hybrid_cell_reports_the_manifests_metrics(files, lines, trace):
    """The two new readers are listed, found by name and run; a CPU
    trace has no device plane, so they return nothing and are left out
    of the line, never invented."""
    section = "per_layer" if trace else "end_to_end"
    listed = files.metrics_of(section, CELL)
    if trace:
        assert {"prefill_chunk_ms", "prefill_roofline_share"} \
            <= {m["name"] for m in listed}
    wanted = {m["name"]: m["unit"] for m in listed
              if m["source"] != "device_trace"}
    got = {k: v["unit"] for k, v in lines[trace]["metrics"].items()}
    assert got == wanted
    assert all(v["value"] > 0 for v in lines[trace]["metrics"].values())


def test_hybrid_cell_prefilled_in_chunks(files, tmp_path):
    """The rehearsal's prompts are longer than its chunk: the driver
    path above ran prefill programs at the chunk's width from a carried
    state, and the warm-up reached every width the window used."""
    from benchmarks.drivers import open_loop
    ctx, _ = bench_run.make_context(
        files, CELL, SEED, 1.0, True, jax.devices()[:1],
        CHIP_PEAKS["TPU v5 lite"], str(tmp_path))
    model, mcfg, eng, programs = open_loop.build(ctx)
    try:
        assert eng.core.prefill_chunk == 16
        assert eng.core.pool.state_bytes_per_slot \
            == jamba.facts(ctx.config)["state_bytes_per_slot"]
        chunks = [s.attrs for s in eng.tracer.spans()
                  if s.name == "prefill_chunk"]
        assert any(a["state_carried"] for a in chunks)
        assert {a["width"] for a in chunks} == {8, 16}
        assert programs["prefill"] == 2 and programs["decode"] == 1
    finally:
        eng.close()
