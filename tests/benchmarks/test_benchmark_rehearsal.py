"""Each kind of run end to end on the CPU at ``gpt_tiny`` / ``llama_tiny``
size, through a test-only manifest, configuration, traffic and metric
files under ``tests/benchmarks/rehearsal/`` that ``run.py`` finds by name
without any edit: the way a later PR adds a cell.  The TPU check lives in
``run.py``'s ``main()`` alone, as in ``chip_smoke.py``."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import traffic
from benchmarks.lib.compile_clock import CompileClock
from benchmarks.lib.peaks import CHIP_PEAKS

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK.json")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
SEED = 2 ** 31 + 17         # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def files():
    return bench_run.Files(MANIFEST)


@pytest.fixture(scope="module")
def lines(files, tmp_path_factory):
    """Every rehearsal cell run once untraced and once traced; the
    hybrid cell on four of the virtual CPU devices."""
    out = str(tmp_path_factory.mktemp("out"))
    made = {}
    for cell in ("gpt-tiny.serve", "llama-tiny.train",
                 "gpt-tiny.train-hybrid"):
        for trace in (False, True):
            if trace and cell == "gpt-tiny.train-hybrid":
                continue
            line = bench_run.run_cell(
                files, cell, SEED, 2.0, trace, jax.devices()[:4],
                CHIP_PEAKS["TPU v5 lite"], out)
            # what main() prints is this object as one JSON line
            made[cell, trace] = json.loads(json.dumps(line))
    return made


CELLS = [("gpt-tiny.serve", False), ("gpt-tiny.serve", True),
         ("llama-tiny.train", False), ("llama-tiny.train", True),
         ("gpt-tiny.train-hybrid", False)]


@pytest.mark.parametrize("cell, trace", CELLS)
def test_last_line_has_exactly_the_contracts_keys(lines, cell, trace):
    line = lines[cell, trace]
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert set(line["device"]) == DEVICE_KEYS | (
        {"busy_s", "window_s"} if trace else set())
    assert line["device"]["platform"] == "cpu"
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float) and value["value"] > 0
    if trace:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
        assert line["device"]["window_s"] > 0


@pytest.mark.parametrize("cell, trace", CELLS)
def test_cell_is_correct_and_nothing_failed(lines, cell, trace):
    line = lines[cell, trace]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("cell, trace", CELLS)
def test_metrics_are_the_manifests_for_that_run(files, lines, cell, trace):
    section = "per_layer" if trace else "end_to_end"
    wanted = {m["name"]: m["unit"]
              for m in files.metrics_of(section, cell)}
    got = {k: v["unit"] for k, v in lines[cell, trace]["metrics"].items()}
    # no device trace on the CPU: a reader that finds nothing to read
    # returns nothing and its metric is left out, never invented
    assert got == wanted
    if not trace:
        assert "setup_s" in got and len(got) >= 2


def test_added_metric_file_is_found_by_name(lines):
    """``steps_counted`` exists only under tests/benchmarks/rehearsal/."""
    got = lines["llama-tiny.train", True]["metrics"]
    assert got["steps_counted"]["value"] >= 1


def test_every_reader_of_the_real_manifest_loads():
    """Each per-layer metric of BENCHMARK.json has a reader that
    ``run.py`` finds by name; given a run with nothing to read it
    returns nothing, and the metric is left out of the line."""
    real = bench_run.Files(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in real.manifest["per_layer"]]
    assert names
    for name in names:
        reader = real.module(f"metrics/{name}.py")
        assert reader.read({}) is None, name
    ttft = real.module("metrics/ttft_p95_ms.py")
    run = {"ttft_by_due": [(0.5, 0.1), (1.0, 0.2), (7.5, 9.0)],
           "window": (100.0, 140.0), "trace_clock": (108.0, 111.0)}
    # the request due at 7.5 s met the profiler's stall: it does not count
    assert ttft.read(run) == pytest.approx(200.0)
    assert ttft.read({**run, "trace_clock": None}) == pytest.approx(9000.0)


def test_missing_file_is_named(files, tmp_path):
    with pytest.raises(FileNotFoundError, match="traffic/absent.json"):
        files.find("traffic/absent.json")
    with pytest.raises(KeyError, match="no-such-cell"):
        files.entry("workloads", "no-such-cell")


def test_main_refuses_without_a_tpu(capsys):
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    with pytest.raises(SystemExit) as exit_:
        bench_run.main(["--workload", cell, "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert exit_.value.code not in (0, None)
    assert "needs a TPU" in str(exit_.value.code)
    out = capsys.readouterr().out.strip().splitlines()
    assert out and out[0].startswith("platform=cpu")
    assert not any(l.startswith("{") for l in out)     # no result line


# ----------------------------------------------------------------- traffic

@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(bench_run.ROOT, "benchmarks", "traffic",
                           "serve-chat.json")) as f:
        return json.load(f)


def flat(schedule):
    return [(a.due_s, a.prompt.tolist(), a.max_new_tokens)
            for a in schedule]


def test_schedule_is_a_pure_function_of_the_seed(mix):
    a = traffic.open_loop_schedule(mix, SEED, 20.0, 50304)
    b = traffic.open_loop_schedule(mix, SEED, 20.0, 50304)
    assert flat(a) == flat(b)
    assert len(a) == traffic.request_count(mix, 20.0)
    due = [x.due_s for x in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 20.0


def test_second_seed_gives_other_requests_of_the_same_sizes(mix):
    a = traffic.open_loop_schedule(mix, 1, 20.0, 50304)
    b = traffic.open_loop_schedule(mix, 2, 20.0, 50304)
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in b]
    # every seed serves the same sizes at the same instants: the amount
    # of work, and the queue a tail measures, do not depend on the seed
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in b]


def test_sizes_stay_inside_the_files_limits(mix):
    s = traffic.open_loop_schedule(mix, 5, 40.0, 50304)
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in s)
    assert all(o["min"] <= x.max_new_tokens <= o["max"] for x in s)
    assert all(0 <= x.prompt.min() and x.prompt.max() < 50304 for x in s)


def test_corpus_is_seeded():
    spec = {"corpus_batches": 3, "batch": 2, "seq": 16}
    a, b = traffic.corpus(spec, SEED, 256), traffic.corpus(spec, SEED, 256)
    assert a.shape == (3, 2, 17) and np.array_equal(a, b)
    assert not np.array_equal(a, traffic.corpus(spec, SEED + 1, 256))


def test_compile_clock_sees_a_new_program():
    with CompileClock() as clock:
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert clock.programs >= 1
    with CompileClock() as clock:
        pass
    assert clock.snapshot()["programs"] == 0
