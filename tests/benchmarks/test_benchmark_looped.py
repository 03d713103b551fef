"""What PR 28 adds to the benchmark, in files of its own (a PR adds to
the benchmark and edits nothing it has): the looped family's counts
(``builders/ouro.py``: applied apart from stored), the configuration file
against the catalog row it was copied from, the ``loop_pass_ms`` reader
on hand-made runs, and the new cell's driver path end to end on the CPU
through a rehearsal manifest of its own
(``tests/benchmarks/rehearsal_looped/``)."""

import json
import os

import jax
import pytest

from benchmarks import run as bench_run
from benchmarks.builders import ouro
from benchmarks.lib import flops_bytes, peaks
from benchmarks.lib.peaks import CHIP_PEAKS

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_looped")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "ouro-tiny.serve"
SEED = 2 ** 31 + 29         # the driver's seeds pass 32 signed bits
MS = 1e-3


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ------------------------------------------------------------ the counts

def test_ouro_counts_applied_apart_from_stored():
    """A looped model: what a decode step READS (every block weight once
    a pass) against what memory HOLDS."""
    facts = ouro.facts(load(ROOT, "benchmarks", "configs", "ouro-2.6b.json"))
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert block == 51_388_416
    # ISSUE 28: 48 x block + 2 x 100,663,296 + 2,048 + 2,049
    assert facts["stored_params"] == 2_667_974_657
    assert facts["lookup_params"] == 49152 * 2048
    assert facts["layers"] == 192
    assert facts["matmul_params"] == 4 * (48 * block + 2048 + 2049) \
        + 49152 * 2048
    # one cached row: 192 planes x (K, V) x 16 x 128 x 2 B = 1.5 MiB
    assert flops_bytes.kv_row_bytes(facts) == 1536 * 1024
    byts = flops_bytes.decode_step_bytes(facts, 7, 1500)
    # 19.94 GB of weights read + 1507 rows x 1.5 MiB
    assert byts == pytest.approx(19.94e9 + 1507 * 1572864, rel=0.002)
    t, bound = flops_bytes.least_time_s(
        flops_bytes.decode_step_flops(facts, 7, 1500), byts,
        peaks.chip_peaks("TPU v5 lite"))
    assert bound == "hbm" and t == pytest.approx(0.0272, rel=0.01)


def test_ouro_facts_count_the_models_real_parameters():
    from benchmarks.lib.build import build_model
    from paddle_tpu.nn.functional_call import state
    cfg = load(REHEARSAL, "configs", "ouro-tiny.json")
    model, mcfg = build_model(ouro, cfg, seed=3)
    real = sum(v.size for v in state(model)[0].values())
    facts = ouro.facts(cfg)
    assert facts["stored_params"] == real == mcfg.num_params()
    assert facts["layers"] == mcfg.num_cache_layers == 9
    # applied: the stack, the final norm and the gate 3 times, the head once
    per_pass = real - 2 * 128 * 64
    assert facts["matmul_params"] == 3 * per_pass + 128 * 64


def test_a_catalog_models_file_holds_every_published_number():
    """Where a configuration's ``source`` is a row of the builders'
    catalog, its file holds every number of the row's ``config`` under
    the same key, but for the keys it lists as ``reduced``."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = {r["source_url"]: r["config"]
                for r in map(json.loads, filter(str.strip, f))}
    checked = []
    for c in load(ROOT, "BENCHMARK.json")["configs"]:
        published = rows.get(c["source"])
        if published is None:
            continue
        body = load(ROOT, c["file"])
        for key, value in published.items():
            if key in c["reduced"] or isinstance(value, bool) \
                    or not isinstance(value, (int, float)):
                continue
            assert key in body and body[key] == value, (c["name"], key)
        checked.append(c["name"])
    assert "ouro-2.6b" in checked


# ------------------------------------------------------------ loop_pass_ms

@pytest.fixture(scope="module")
def read():
    return bench_run.Files(os.path.join(ROOT, "BENCHMARK.json")).module(
        "metrics/loop_pass_ms.py").read


def looped_run(passes, programs, clock=(10.0, 13.0)):
    """Decoding ``serving.step`` spans of ``passes`` as the serving
    driver hands them over, ``programs`` ``(name, start ns,
    milliseconds)`` on the device's module line."""
    spans = []
    for k, n in enumerate(passes):
        attrs = {"step": k, "active_slots": 4, "prefill_tokens": 0}
        if n is not None:
            attrs["loop_passes"] = n
        spans.append(("serving.step", 10.0 + 0.1 * k, 10.05 + 0.1 * k,
                      attrs))
    log = []
    return {"spans": spans, "window": (10.0, 20.0), "log": log.append,
            "lines": log, "trace_clock": clock,
            "trace_window_ns": (0, 10 ** 10),
            "decode_module_prefix": "jit_decode",
            "trace": {"host": [], "devices": {0: {"ops": [], "modules": [
                (name, start, int(ms * 1e6))
                for name, start, ms in programs]}}}}


def test_loop_pass_ms_is_the_decode_program_over_the_passes(read):
    run = looped_run([4, 4, 4], [("jit_decode(7)", 0, 60.0),
                                 ("jit_decode(7)", 10 ** 8, 64.0),
                                 ("jit_prefill(3)", 2 * 10 ** 8, 900.0)])
    assert read(run) == pytest.approx(15.5)
    assert "3 decoding steps of 4 passes, device 62.000 ms" in run["lines"][0]
    # an unlooped model: a pass is the step
    assert read(looped_run([1, 1], [("jit_decode(7)", 0, 26.0)])) \
        == pytest.approx(26.0)


def test_loop_pass_ms_counts_decoding_steps_of_the_slice_only(read):
    run = looped_run([2, 4, 4], [("jit_decode(7)", 0, 60.0)],
                     clock=(10.05, 13.0))       # step 0 lies before it
    run["spans"].append(("serving.step", 10.5, 10.51,
                         {"step": 9, "active_slots": 0, "loop_passes": 0}))
    assert read(run) == pytest.approx(15.0)


def test_loop_pass_ms_gives_nothing_without_its_input(read):
    programs = [("jit_decode(7)", 0, 60.0)]
    assert read({"spans": None}) is None                    # untraced
    # the parent: step spans with counts, no loop_passes among them
    assert read(looped_run([None, None], programs)) is None
    # the CPU rehearsal: spans, no device plane
    assert read({**looped_run([4], programs),
                 "trace": {"host": [], "devices": {}}}) is None
    assert read(looped_run([4], [("jit_other", 0, 60.0)])) is None
    assert read({**looped_run([4], programs), "trace_clock": None}) is None


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
def test_looped_cell_is_correct_on_the_cpu(lines, trace):
    line = lines[trace]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("trace", [False, True])
def test_looped_cell_reports_the_manifests_metrics(files, lines, trace):
    """``loop_pass_ms`` is listed, found by name and run; a CPU trace
    has no device plane, so it returns nothing and is left out of the
    line, never invented."""
    section = "per_layer" if trace else "end_to_end"
    listed = files.metrics_of(section, CELL)
    if trace:
        assert "loop_pass_ms" in [m["name"] for m in listed]
    wanted = {m["name"]: m["unit"] for m in listed
              if m["source"] != "device_trace"}
    got = {k: v["unit"] for k, v in lines[trace]["metrics"].items()}
    assert got == wanted
    assert all(v["value"] > 0 for v in lines[trace]["metrics"].values())
