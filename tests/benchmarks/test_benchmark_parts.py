"""The readers of device time by model part (PR 36), on hand-made traces:
``lib/parts.by_part`` (leaf events only, one table per compile, an
unknown name raises), the tables' two sources (the engine's
``program.parts`` spans; the ``tf_op`` stat of an ``.xplane.pb``'s event
metadata, decoded from a file this test encodes by hand), and each new
metric file's ``read`` on a run that has them and on one that has not."""

import os
import struct

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import parts, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FILES = bench_run.Files(os.path.join(ROOT, "BENCHMARK.json"))
NEW_METRICS = ("scope_coverage.serve", "decode_head_ms",
               "decode_attention_ms", "prefill_attention_ms",
               "scope_coverage.train", "recomputed_forward_ms",
               "head_loss_ms", "optimizer_ms")


def op(name, typ="f32[8]{0}", opcode="fusion"):
    """An operation's name as a device trace prints it."""
    return f"%{name} = {typ} {opcode}(f32[8]{{0}} %x), kind=kLoop"


def key(name, typ="f32[8]{0}"):
    return f"%{name} = {typ}"


# a program of 100 ns: a matmul, then a while of 60 ns over two bodies of
# 25 ns each (5 ns of the loop's own), then an unscoped copy
OPS = [(op("dot.1"), 1000, 20),
       (op("while.2", "(s32[], f32[8]{0})", "while"), 1020, 60),
       (op("body_a.3"), 1025, 25),
       (op("body_b.4"), 1050, 25),
       (op("copy.5", opcode="copy"), 1080, 10)]
TABLE = {key("dot.1"): ("head", "forward"),
         key("while.2", "(s32[], f32[8]{0})"): ("unscoped", "forward"),
         key("body_a.3"): ("attention", "forward"),
         key("body_b.4"): ("mlp", "forward"),
         key("copy.5"): ("unscoped", "forward")}


def trace_of(ops, modules):
    return {"devices": {0: {"ops": list(ops), "modules": list(modules)}},
            "host": []}


# ---- (c) by_part --------------------------------------------------------
def test_leaves_drop_the_container_and_empty_events():
    kept = parts.leaves(OPS + [(op("nothing.6"), 1025, 0)])
    assert [e[0] for e in kept] == [OPS[0][0], OPS[2][0], OPS[3][0],
                                   OPS[4][0]]


def test_by_part_counts_a_loop_once():
    row = parts.by_part(trace_of(OPS, [("jit_decode(7)", 1000, 100)]),
                        "jit_decode", TABLE)
    assert row["programs"] == 1
    assert row["mean_ms"] == pytest.approx(100e-6)
    # 20 + 25 + 25 + 10: the while's 60 are its bodies', not added
    assert row["ops_ms"] == pytest.approx(80e-6)
    assert row["parts"] == {
        ("attention", "forward"): pytest.approx(25e-6),
        ("mlp", "forward"): pytest.approx(25e-6),
        ("head", "forward"): pytest.approx(20e-6),
        ("unscoped", "forward"): pytest.approx(10e-6)}
    assert row["unscoped_ms"] == pytest.approx(10e-6)
    assert row["unscoped"] == [[OPS[4][0], pytest.approx(10e-6)]]
    assert parts.part_ms(row, "attention", "mlp") == pytest.approx(50e-6)
    assert parts.part_ms(row, phase="forward") == pytest.approx(80e-6)
    assert parts.coverage_percent([row]) == pytest.approx(87.5)


def test_by_part_means_over_programs_and_keeps_to_the_window():
    shifted = [(n, s + 1000, d) for n, s, d in OPS]
    outside = [(n, s + 5000, d) for n, s, d in OPS]
    trace = trace_of(
        OPS + shifted + outside + [(op("other.9"), 3000, 50)],
        [("jit_decode(7)", 1000, 100), ("jit_decode(7)", 2000, 100),
         ("jit_prefill(8)", 3000, 50), ("jit_decode(7)", 6000, 100)])
    row = parts.by_part(trace, "jit_decode", [TABLE], 900, 5000)
    assert row["programs"] == 2 and row["ops_ms"] == pytest.approx(80e-6)
    assert parts.by_part(trace, "jit_step", TABLE)["programs"] == 0


def test_by_part_raises_on_a_name_no_table_holds():
    table = {k: v for k, v in TABLE.items() if "body_b" not in k}
    with pytest.raises(ValueError, match="in no table.*body_b"):
        parts.by_part(trace_of(OPS, [("jit_decode(7)", 1000, 100)]),
                      "jit_decode", table)


def test_by_part_pairs_each_program_with_its_own_compile():
    """Two widths of one function: the same instruction names, other
    shapes, and ``%fused.3`` is another part in each."""
    def ops(width, t0):
        t = f"f32[{width}]{{0}}"
        return [(op("dot.1", t), t0, 30), (op("fused.3", t), t0 + 30, 10)]

    def table(width, part):
        t = f"f32[{width}]{{0}}"
        return {key("dot.1", t): ("attention", "forward"),
                key("fused.3", t): (part, "forward")}

    trace = trace_of(ops(16, 0) + ops(32, 100),
                     [("jit_prefill(1)", 0, 40), ("jit_prefill(2)", 100, 40)])
    row = parts.by_part(trace, "jit_prefill",
                        [table(16, "mlp"), table(32, "head")])
    assert row["parts"] == {("attention", "forward"): pytest.approx(30e-6),
                            ("mlp", "forward"): pytest.approx(5e-6),
                            ("head", "forward"): pytest.approx(5e-6)}
    # two compiles that both hold every name and disagree cannot be told
    with pytest.raises(ValueError, match="disagree"):
        parts.by_part(trace, "jit_prefill",
                      [table(16, "mlp"), table(32, "head"),
                       table(16, "head")])
    # ... and agree: either serves
    parts.by_part(trace, "jit_prefill",
                  [table(16, "mlp"), table(32, "head"), table(16, "mlp")])


# ---- the serving readers ------------------------------------------------
def serving_run(with_tables=True, log=None):
    """What ``drivers/open_loop.run`` + ``run.reduce_trace`` hand the
    readers, around one decode and one prefill program."""
    prefill_ops = [(op("qkv.1", "f32[16]{0}"), 2000, 40),
                   (op("scatter.2", "f32[16]{0}"), 2040, 10)]
    prefill_table = {key("qkv.1", "f32[16]{0}"): ("attention", "forward"),
                     key("scatter.2", "f32[16]{0}"): ("kv_append",
                                                      "forward")}
    spans = [("serving.step", 0.0, 1.0, {"active_slots": 1})]
    if with_tables:
        # as the tracer's ring holds them: lists after a JSON round trip
        spans += [("program.parts", 0.0, 0.0,
                   {"program": "jit_decode",
                    "parts": {k: list(v) for k, v in TABLE.items()}}),
                  ("program.parts", 0.0, 0.0,
                   {"program": "jit_prefill", "parts": prefill_table})]
    lines = []
    return {"trace": trace_of(OPS + prefill_ops,
                              [("jit_decode(7)", 1000, 100),
                               ("jit_prefill(8)", 2000, 50)]),
            "trace_window_ns": (0, 10000), "trace_clock": (0.0, 1.0),
            "spans": spans, "decode_module_prefix": "jit_decode",
            "log": lines.append if log is None else log, "lines": lines}


def read(metric, run):
    return FILES.module(f"metrics/{metric}.py").read(run)


def test_serving_metrics_read_the_spans_tables():
    run = serving_run()
    assert read("decode_head_ms", run) == pytest.approx(20e-6)
    assert read("decode_attention_ms", run) == pytest.approx(25e-6)
    assert read("prefill_attention_ms", run) == pytest.approx(50e-6)
    # (80 - 10) + 50 of 130 ns of operations
    assert read("scope_coverage.serve", run) == pytest.approx(
        100 * 120 / 130)
    # each file put the whole table on an earlier line
    assert sum("head.forward" in line for line in run["lines"]) == 3
    assert any(line.startswith("prefill_attention_ms: jit_prefill")
               and "kv_append.forward" in line for line in run["lines"])


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_reads_nothing_without_a_table(metric):
    """The parent's run (no span, no scope) and the CPU rehearsals (no
    device trace): nothing, and no error."""
    assert read(metric, {}) is None
    if metric in NEW_METRICS[:4]:
        assert read(metric, serving_run(with_tables=False)) is None
        assert read(metric, dict(serving_run(), trace=None)) is None
    else:
        assert read(metric, {"trace": None, "log": print,
                             "step_module_prefix": "jit_step"}) is None


def test_manifest_lists_the_new_metrics_where_they_read():
    """Seven of the eight readers are listed.  The two chunked-prefill
    cells are NOT among the serving ones' ``workloads`` and
    ``prefill_attention_ms``, which reads in those two alone, has no
    entry: ``test_benchmark_hybrid.py`` and ``test_benchmark_moe.py``
    pin their cells' per-layer metrics as exact sets (PERF.md section 7
    holds the line for the next ``benchmark`` issue)."""
    listed = [n for n in NEW_METRICS if n != "prefill_attention_ms"]
    by_name = {m["name"]: m for m in FILES.manifest["per_layer"]}
    for name in NEW_METRICS:
        assert os.path.isfile(FILES.find(f"metrics/{name}.py"))
    for name in listed:
        assert by_name[name]["source"] == "device_trace"
    for name in listed[:3]:
        assert by_name[name]["moves"] == "itl_p95_ms"
        assert by_name[name]["workloads"] == [
            "gpt3-6.7b.serve-chat", "ouro-2.6b.serve-reason-512"]
    for name in listed[3:]:
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert by_name[name]["workloads"] == ["mistral-7b.train-4k"]
    assert "prefill_attention_ms" not in by_name
    # nothing that was there moved: the new entries are the list's tail
    assert [m["name"] for m in FILES.manifest["per_layer"]][-7:] == listed


# ---- the training reader: an .xplane.pb encoded by hand ------------------
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: an int as a varint, bytes / str / a nested
    message (bytes) length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(k, message):
    return field(1, k) + field(2, message)


def plane(name, metadata, lines, stat_names=()):
    """``metadata``: ``{id: (name, [(stat id, value), ...])}``;
    ``lines``: ``[(name, [(metadata id, offset ns, duration ns), ...])]``."""
    out = field(2, name)
    for i, (line, events) in enumerate(lines):
        body = field(1, i + 1) + field(2, line) + field(3, 0)
        for mid, start, dur in events:
            body += field(4, field(1, mid) + field(2, start * 1000)
                          + field(3, dur * 1000))
        out += field(3, body)
    for mid, (mname, stats) in metadata.items():
        meta = field(1, mid) + field(2, mname)
        for sid, value in stats:
            meta += field(5, field(1, sid) + (
                field(3, value) if isinstance(value, int)
                else field(5, value)))
        out += field(4, entry(mid, meta))
    for sid, sname in enumerate(stat_names, 1):
        out += field(5, entry(sid, field(1, sid) + field(2, sname)))
    return field(1, out)


STEP_OPS = [("dot.1", "jit(step)/jvp(head)/dot_general:", 20),
            ("fusion.2", "jit(step)/transpose(jvp(jvp()))/checkpoint/"
             "rematted_computation/mlp/mul:", 30),
            ("fusion.3", "jit(step)/transpose(jvp(loss))/sub:", 10),
            ("fusion.4", "jit(step)/optimizer/mul:", 25),
            ("copy.5", "", 5)]


def write_trace(root, cell, scoped=True, window=(500, 5000)):
    """A trace as ``jax.profiler`` leaves one under ``out/trace/<cell>``:
    two ``jit_step`` programs of 90 ns on device 0, the window's mark on
    the host."""
    stats = ["tf_op", "program_id"]
    metadata = {1: ("jit_step(77)", [])}
    ops, t = [], 1000
    for rep in range(2):
        for i, (name, op_name, dur) in enumerate(STEP_OPS):
            if not scoped:
                op_name = "jit(step)/jvp()/mul:" if op_name else ""
            metadata[10 + i] = (op(name), [(1, op_name), (2, 77)])
            ops.append((10 + i, t, dur))
            t += dur
        t = 2000
    # an operation of ANOTHER program under the same name
    metadata[30] = (op("dot.1"), [(1, "jit(other)/mlp/dot_general:"),
                                  (2, 78)])
    device = plane("/device:TPU:0", metadata,
                   [("XLA Modules", [(1, 1000, 90), (1, 2000, 90)]),
                    ("XLA Ops", ops)], stats)
    host = plane("/host:CPU", {1: (xplane.WINDOW_MARK, [])},
                 [("main", [(1, window[0], window[1] - window[0])])])
    folder = os.path.join(root, cell, "plugins", "profile", "2026_10_04")
    os.makedirs(folder)
    path = os.path.join(folder, "vm.xplane.pb")
    with open(path, "wb") as f:
        f.write(device + host)
    return path


def training_run(path, lines):
    trace = xplane.load(path)
    mark, = [e for e in trace["host"] if e[0] == xplane.WINDOW_MARK]
    return {"trace": trace, "trace_window_ns": (mark[1], mark[1] + mark[2]),
            "step_module_prefix": "jit_step", "log": lines.append}


def test_event_op_names_decodes_the_metadata(tmp_path):
    path = write_trace(str(tmp_path), "cell")
    programs = parts.event_op_names(path)
    # the program's own event carries no program id
    assert set(programs) == {None, 77, 78}
    assert programs[None] == {"jit_step(77)": ""}
    assert programs[77][op("fusion.4")] == "jit(step)/optimizer/mul"
    assert programs[77][op("copy.5")] == ""
    assert programs[78] == {op("dot.1"): "jit(other)/mlp/dot_general"}
    # the host plane holds no such stat
    assert parts.event_op_names(path, "/host:CPU") == {None: {
        xplane.WINDOW_MARK: ""}}


def test_training_metrics_read_the_trace_files_own_op_names(
        tmp_path, monkeypatch):
    monkeypatch.setattr(parts, "TRACE_ROOT", str(tmp_path))
    # another cell's older trace lies beside it: told apart by the mark
    write_trace(str(tmp_path), "other-cell", window=(400, 5000))
    path = write_trace(str(tmp_path), "train-cell")
    lines = []
    run = training_run(path, lines)
    assert parts.trace_file(run) == path
    assert read("optimizer_ms", run) == pytest.approx(25e-6)
    assert read("head_loss_ms", run) == pytest.approx(30e-6)
    assert read("recomputed_forward_ms", run) == pytest.approx(30e-6)
    assert read("scope_coverage.train", run) == pytest.approx(100 * 85 / 90)
    assert any("head.forward" in line and "loss.backward" in line
               for line in lines)


def test_training_metrics_read_nothing_off_an_unscoped_step(
        tmp_path, monkeypatch):
    """The parent's step: every op_name is there, none names a part."""
    monkeypatch.setattr(parts, "TRACE_ROOT", str(tmp_path))
    run = training_run(write_trace(str(tmp_path), "cell", scoped=False), [])
    for metric in NEW_METRICS[4:]:
        assert read(metric, run) is None
    # and where the file cannot be told, nothing either
    lost = dict(run, trace_window_ns=(1, 2))
    lost.pop("parts_row")
    assert parts.trace_file(lost) is None
    assert read("optimizer_ms", lost) is None


def test_decoder_steps_over_fixed_width_fields():
    # the encoder above writes varints and length-delimited fields only
    blob = varint(9 << 3 | 1) + struct.pack("<d", 1.5) + field(2, "name")
    assert list(parts._fields(blob)) == [(9, struct.pack("<d", 1.5)),
                                         (2, b"name")]
