"""BENCHMARK.json against every rule the benchmark's contract states:
written FIRST (ISSUE 24), because PR 22 was refused for one layer name
with a space in it after all its work was done."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# a width: a hidden, intermediate, latent, state or projection size, a
# head size, an expansion factor, the number of experts per token
WIDTH_ENDS = ("_size", "_dim", "_rank", "_mult", "_width")
WIDTH_WORDS = ("expansion", "experts_per_tok", "d_model", "d_ff", "d_head")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def line(text, most=200):
    return isinstance(text, str) and 1 <= len(text) <= most \
        and "\n" not in text and "\t" not in text


def cells_of(metric, manifest):
    return metric.get("workloads",
                      [w["name"] for w in manifest["workloads"]])


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}


def test_command_and_paths(manifest):
    paths, command = manifest["paths"], manifest["command"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") \
            and ".." not in p.split("/"), p
        assert os.path.isdir(os.path.join(ROOT, p)), p
    assert 1 <= len(command) <= 32
    for word in command:
        assert line(word) and not word.startswith("/") \
            and ".." not in word.split("/"), word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in paths), \
                f"{word} is a file of the repo outside paths"


def test_run_seconds(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and not isinstance(rs, bool)
    assert 10 <= rs <= 51
    # a full check with all 24 cells must fit: (2 + 14 x 24) runs of
    # run_seconds + 60, 24 x 180 s to compile, 1200 s spare, in 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    files = [c["file"] for c in configs]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert NAME.match(c["name"]), c["name"]
        assert line(c["source"]) and line(c["why"])
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert PATH.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert all(NAME.match(part.replace("/", "_"))
                   for part in c["file"].split("/")), c["file"]
        assert isinstance(c["reduced"], list) and len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert isinstance(body, dict)
        for key in c["reduced"]:
            assert NAME.match(key), key
            assert key in body, f"{key} is not a key of {c['file']}"
            low = key.lower()
            assert not low.endswith(WIDTH_ENDS) and not any(
                w in low for w in WIDTH_WORDS), f"{key} names a width"
        # the file says for itself what it cut, and from what
        assert set(body.get("reduced", {})) == set(c["reduced"])


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert line(w["why"])
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(len(cells) // 4, 1)


def test_end_to_end(manifest):
    metrics = manifest["end_to_end"]
    assert 1 <= len(metrics) <= 16
    cells = {w["name"] for w in manifest["workloads"]}
    for m in metrics:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.1
        assert set(cells_of(m, manifest)) <= cells
    setup = [m for m in metrics if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["better"] == "lower" and setup[0]["unit"] == "s"
    for cell in cells:
        reported = [m["name"] for m in metrics
                    if cell in cells_of(m, manifest)]
        assert "setup_s" in reported and len(reported) >= 2, cell


def test_per_layer(manifest):
    metrics = manifest["per_layer"]
    assert 1 <= len(metrics) <= 128
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in metrics:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        # PR 22 was refused for a layer with a space in it
        assert NAME.match(m["layer"]), m["layer"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        mine = set(cells_of(m, manifest))
        assert mine and mine <= cells
        assert mine <= set(cells_of(e2e[m["moves"]], manifest)), \
            f"{m['name']} moves {m['moves']}, which not all its cells report"
        if m["name"].endswith("_roofline") or "mfu" in m["name"] \
                or "roofline" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in cells_of(m, manifest) for m in metrics), cell


def test_no_two_metrics_share_a_name(manifest):
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(names)) == len(names)


def test_every_file_a_cell_needs_exists(manifest):
    bench = os.path.join(ROOT, "benchmarks")
    for w in manifest["workloads"]:
        path = os.path.join(bench, "traffic", w["traffic"] + ".json")
        assert os.path.isfile(path), path
        with open(path) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(bench, "drivers", kind + ".py"))
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            builder = json.load(f)["builder"]
        assert os.path.isfile(os.path.join(bench, "builders",
                                           builder + ".py"))
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_files_under_paths_have_plain_names(manifest):
    """A file under ``paths`` is named from the characters of a name and
    ``/``; what building and running leave behind is git-ignored."""
    ok = re.compile(r"^[A-Za-z0-9_.\-]+$")
    for p in manifest["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", "out")]
            for name in dirs + files:
                if name.endswith(".pyc"):
                    continue
                assert ok.match(name) and name[0] != "." \
                    and name[0] != "-", os.path.join(folder, name)
