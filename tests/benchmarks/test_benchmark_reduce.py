"""The benchmark's arithmetic: exact percentiles, operation and byte
counts against hand-worked numbers for both configurations, and the
trace reduction on a synthetic three-event trace and on the recorded
fixture."""

import glob
import json
import os
import statistics

import pytest

from benchmarks.builders import gpt, llama
from benchmarks.lib import flops_bytes, peaks, stats, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GIB = 1 << 30


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


# ------------------------------------------------------------------- stats

def test_p95_of_200_samples_has_10_beyond_it():
    values = list(range(1, 201))
    p95 = stats.percentile(values, 0.95)
    assert p95 == 190 and sum(v > p95 for v in values) == 10


def test_percentile_is_a_sample_and_order_free():
    values = [5.0, 1.0, 9.0, 3.0]
    assert stats.percentile(values, 0.5) == 3.0
    assert stats.percentile(values, 1.0) == 9.0
    assert stats.percentile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.95)
    with pytest.raises(ValueError):
        stats.percentile(values, 0.0)


def test_iqr_share_is_the_contracts_spread():
    values = [100.0, 101.0, 99.0, 103.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.iqr_share(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


# ------------------------------------------------------------- flops, bytes

def test_gpt3_d8_counts():
    facts = gpt.facts(config("gpt3-6.7b-d8.json"))
    # 8 x (4 x 4096^2 + 2 x 4096 x 16384) = 1.611B in the blocks' matrices,
    # 50304 x 4096 = 206M in the tied table, 8.4M learned positions
    assert flops_bytes.total_params(facts) == pytest.approx(1.82e9, rel=0.005)
    assert facts["lookup_params"] == 2048 * 4096
    # one cached row: 8 layers x (K, V) x 4096 x 2 B = 128 KiB
    assert flops_bytes.kv_row_bytes(facts) == 128 * 1024
    old = flops_bytes.train_flops_per_token(facts, 2048, "6n12les")
    assert old == pytest.approx(11.7e9, rel=0.005)
    need = flops_bytes.train_flops_per_token(facts, 2048, "required")
    # 6 x (1.8255B - 8.4M of positions) + 6 x 8 x 4096 x 2048
    assert need == pytest.approx(6 * 1.8171e9 + 0.4027e9, rel=0.002)
    assert need < old


def test_mistral_d2_counts():
    facts = llama.facts(config("mistral-7b-d2.json"))
    # a block: q, o 2 x 4096^2, k, v 2 x 4096 x 1024, SwiGLU 3 x 4096 x
    # 14336 = 218.1M; embedding and head 131M each
    assert flops_bytes.total_params(facts) == pytest.approx(698e6, rel=0.003)
    assert facts["lookup_params"] == 32000 * 4096
    # 2 layers x (K, V) x 8 kv heads x 128 x 2 B = 8 KiB
    assert flops_bytes.kv_row_bytes(facts) == 8 * 1024
    old = flops_bytes.train_flops_per_token(facts, 4096, "6n12les")
    assert old == pytest.approx(4.6e9, rel=0.005)
    need = flops_bytes.train_flops_per_token(facts, 4096, "required")
    # the input embedding is a lookup, causal attention half a square
    assert need == pytest.approx(6 * 567.3e6 + 6 * 2 * 4096 * 4096, rel=0.002)


@pytest.mark.parametrize("builder, name", [(gpt, "gpt-tiny.json"),
                                           (llama, "llama-tiny.json")])
def test_facts_count_the_models_real_parameters(builder, name):
    from benchmarks.lib.build import build_model
    from paddle_tpu.nn.functional_call import state
    with open(os.path.join(os.path.dirname(__file__), "rehearsal",
                           "configs", name)) as f:
        cfg = json.load(f)
    model, _ = build_model(builder, cfg, seed=3)
    real = sum(v.size for v in state(model)[0].values())
    assert flops_bytes.total_params(builder.facts(cfg)) == real


def test_decode_step_bound_by_hbm_at_chat_sizes():
    facts = gpt.facts(config("gpt3-6.7b-d8.json"))
    table = peaks.chip_peaks("TPU v5 lite")
    byts = flops_bytes.decode_step_bytes(facts, 16, 4800)
    # 1.817B matmul weights x 2 B + (4800 + 16) rows x 128 KiB
    assert byts == pytest.approx(3.634e9 + 4816 * 131072, rel=0.002)
    flops = flops_bytes.decode_step_flops(facts, 16, 4800)
    assert flops == pytest.approx(2 * 1.8171e9 * 16
                                  + 4 * 8 * 4096 * 4800, rel=0.002)
    t, bound = flops_bytes.least_time_s(flops, byts, table)
    assert bound == "hbm" and t == pytest.approx(byts / 819e9)
    t4, _ = flops_bytes.least_time_s(flops, byts, table, chips=4)
    assert t4 == pytest.approx(t / 4)


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.chip_peaks("cpu")


# ------------------------------------------------------------------- trace

SYNTHETIC = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Modules"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 9000000 }
  }
  lines {
    name: "XLA Ops"
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_decode(123)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "all-reduce.2" } }
}
planes {
  name: "/host:CPU"
  lines {
    name: "main"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 7500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "serving.step" } }
}
"""


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    folder = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t0"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return xplane.load(xplane.find_xplane(str(folder.parents[2])))


def test_synthetic_trace_loads(synthetic):
    dev = synthetic["devices"][0]
    assert [e[0] for e in dev["ops"]] == ["fusion.1", "all-reduce.2",
                                          "fusion.1"]
    assert dev["modules"] == [("jit_decode(123)", 1000.0, 9000.0)]
    assert ("bench.window", 0.0, 12000.0) in synthetic["host"]


def test_busy_union_counts_overlap_once(synthetic):
    ops = synthetic["devices"][0]["ops"]
    # [1000, 5000] and [3000, 7000] overlap: [1000, 7000] + [9000, 10000]
    assert xplane.merged(ops) == [[1000.0, 7000.0], [9000.0, 10000.0]]
    assert xplane.busy_ns(ops) == 7000.0
    assert xplane.idle_share(ops, 0.0, 12000.0) == pytest.approx(5 / 12)
    # a window that cuts an event counts only the part inside
    assert xplane.busy_ns(xplane.clip(ops, 4000.0, 9500.0)) == 3500.0


def test_per_module_and_per_op_time(synthetic):
    dev = synthetic["devices"][0]
    assert xplane.module_durations(dev["modules"], "jit_decode") == [9000.0]
    assert xplane.module_durations(dev["modules"], "jit_step") == []
    assert xplane.top_ops(dev["ops"], 1) == [["fusion.1", 5000.0 / 1e9]]


def test_idle_gaps_by_host_span(synthetic):
    ops = synthetic["devices"][0]["ops"]
    host = [e for e in synthetic["host"] if e[0] != "bench.window"]
    gaps = dict(xplane.idle_gaps(ops, 0.0, 12000.0, host, min_gap_ns=0.0))
    # [7000, 9000] has its middle inside serving.step; [0, 1000] and
    # [10000, 12000] are under no span
    assert gaps == {"serving.step": pytest.approx(2e-6),
                    "no_host_span": pytest.approx(3e-6)}
    small = dict(xplane.idle_gaps(ops, 0.0, 12000.0, host, min_gap_ns=1500.0))
    assert small["gaps_under_1.5us"] == pytest.approx(1e-6)


def test_exposed_collective_time(synthetic):
    ops = synthetic["devices"][0]["ops"]
    # all-reduce runs [3000, 7000]; fusion.1 hides [3000, 5000]
    exposed = xplane.exposed_ns(ops, lambda n: n.startswith("all-reduce"))
    assert exposed == 2000.0


def recorded():
    return sorted(glob.glob(os.path.join(
        ROOT, "benchmarks", "fixtures", "*.xplane.pb")))


def test_recorded_fixture_reduces():
    """The small trace recorded on the chip by this benchmark's own
    traced run (trimmed): the reduction finds the device plane, the
    decode program on the modules line, and a busy share inside (0, 1]."""
    paths = recorded()
    assert paths, "no recorded trace under benchmarks/fixtures"
    trace = xplane.load(paths[0])
    dev = trace["devices"][0]
    assert dev["ops"] and dev["modules"]
    marks = [e for e in trace["host"] if e[0] == "bench.window"]
    assert len(marks) == 1
    t0, t1 = marks[0][1], marks[0][1] + marks[0][2]
    idle = xplane.idle_share(dev["ops"], t0, t1)
    assert 0.0 <= idle < 1.0
    assert xplane.busy_ns(xplane.clip(dev["ops"], t0, t1)) <= t1 - t0
    assert xplane.module_durations(dev["modules"], "jit_decode")
    gaps = xplane.idle_gaps(xplane.clip(dev["ops"], t0, t1), t0, t1,
                            [e for e in trace["host"]
                             if e[0] != "bench.window" and e[2] > 0])
    assert sum(s for _, s in gaps) == pytest.approx(
        idle * (t1 - t0) / 1e9, rel=1e-6)
