"""Runtime/static consistency gate for graftcomm (ISSUE 20).

graftcomm (tools/analysis/comm.py) statically derives the comm plane's
collective schedules — ring perm tables and per-hop shard/chunk walks
from an integer mirror of ``ring_schedule``, seam payload bytes from
graftmem formulas, and program schedules from the graftprog shard_map
units.  This test closes the loop from the OTHER side:

  * the mirror equals the LIVE ``ring_schedule(tp)`` line-for-line over
    every reference tp — perm tables, the full entry_src/exit_chunk
    walks of every device, and the tp<1 refusal (type AND message), so
    a ring-schedule edit that is not mirrored in the analysis fails
    here before the manifest silently drifts;
  * the manifest proves the fused (Pallas decode-block) and composed
    (XLA collective-matmul) TP decode paths hop-equivalent: both seam
    roles carry one guarded neighbour-ring ppermute, and the layer
    walks of ``_tp_layer`` and ``tp_fused_block_layer`` traverse the
    same entry/exit role sequence;
  * ``comm_fingerprint`` participates in the parse-cache version: a
    registered comm module invalidates saved caches (stale analysis
    is never served).

zz-prefixed like test_zz_memory_surface: importing the kernels pulls
jax in — sort after the jaxlib-0.4 dispatch-race window conftest
documents.
"""

import os

import pytest

from paddle_tpu.kernels.collective_matmul import ring_schedule
from paddle_tpu.tools.analysis import (RING_REFERENCE_TPS,
                                       build_comm_manifest_for_paths,
                                       comm_fingerprint,
                                       mirror_entry_src,
                                       mirror_exit_chunk,
                                       mirror_ring_perm,
                                       mirror_ring_schedule)

ENTRY_COMPOSED = "paddle_tpu.kernels.collective_matmul.allgather_matmul"
EXIT_COMPOSED = \
    "paddle_tpu.kernels.collective_matmul.matmul_reduce_scatter"
ENTRY_FUSED = "paddle_tpu.kernels.decode_block_tp.ring_entry_matmul"
EXIT_FUSED = "paddle_tpu.kernels.decode_block_tp.ring_exit_matmul"
LAYER_COMPOSED = "paddle_tpu.serving.tp._tp_layer"
LAYER_FUSED = "paddle_tpu.kernels.decode_block_tp.tp_fused_block_layer"


@pytest.fixture(scope="module")
def manifest():
    """The statically-derived seam manifest, built through the same
    library entry point the CLI's ``--comm`` uses."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    scope = [os.path.join(root, p)
             for p in ("paddle_tpu", "scripts")]
    m = build_comm_manifest_for_paths(scope, root=root)
    assert m["order_safety"]["ok"], m["order_safety"]
    return m


# ------------------------------------------------- mirror == live ring

@pytest.mark.parametrize("tp", RING_REFERENCE_TPS)
def test_mirror_ring_matches_live(tp):
    live = ring_schedule(tp)
    assert mirror_ring_perm(tp) == live.perm
    for idx in range(tp):
        for hop in range(tp):
            assert mirror_entry_src(tp, idx, hop) == \
                live.entry_src(idx, hop)
            assert mirror_exit_chunk(tp, idx, hop) == \
                live.exit_chunk(idx, hop)


@pytest.mark.parametrize("tp", RING_REFERENCE_TPS)
def test_mirror_schedule_tables_match_live_walks(tp):
    live = ring_schedule(tp)
    row = mirror_ring_schedule(tp)
    assert row["tp"] == tp
    assert row["is_permutation"]
    assert row["perm"] == [list(p) for p in live.perm]
    for idx in range(tp):
        assert row["entry_src"][str(idx)] == \
            [live.entry_src(idx, hop) for hop in range(tp)]
        assert row["exit_chunk"][str(idx)] == \
            [live.exit_chunk(idx, hop) for hop in range(tp)]
        # the exit ring's final hop lands on the device's OWN chunk —
        # the invariant matmul_reduce_scatter's accumulator relies on
        assert row["exit_chunk"][str(idx)][-1] == idx


@pytest.mark.parametrize("tp", (0, -1))
def test_mirror_refusal_matches_live(tp):
    msg = f"ring needs tp >= 1, got {tp}"
    with pytest.raises(ValueError, match=msg):
        ring_schedule(tp)
    with pytest.raises(ValueError, match=msg):
        mirror_ring_perm(tp)
    with pytest.raises(ValueError, match=msg):
        mirror_ring_schedule(tp)


def test_manifest_ring_mirror_section_is_the_mirror(manifest):
    for tp in RING_REFERENCE_TPS:
        assert manifest["ring_mirror"][f"tp={tp}"] == \
            mirror_ring_schedule(tp)


# ------------------------------- fused vs composed: one ring schedule

def test_fused_and_composed_seams_hop_equivalent(manifest):
    roles = manifest["roles"]
    assert set(roles["entry"]["members"]) == {ENTRY_COMPOSED,
                                             ENTRY_FUSED}
    assert set(roles["exit"]["members"]) == {EXIT_COMPOSED, EXIT_FUSED}
    for role in ("entry", "exit"):
        assert roles[role]["equivalent"], roles[role]
        # one guarded neighbour-ring ppermute: tp-1 in-flight hops
        assert roles[role]["signature"] == ["ppermute:tp-1:neighbor"]


def test_layer_walks_traverse_same_role_sequence(manifest):
    lp = manifest["layer_paths"]
    assert lp[LAYER_COMPOSED]["roles"] == lp[LAYER_FUSED]["roles"]
    # QKV/attention entry+exit then MLP entry+exit — per layer
    assert lp[LAYER_FUSED]["roles"] == ["entry", "exit", "entry",
                                        "exit"]


def test_seam_payloads_scale_inversely_with_tp(manifest):
    for qname in (ENTRY_COMPOSED, EXIT_COMPOSED, ENTRY_FUSED,
                  EXIT_FUSED):
        ladder = manifest["seams"][qname]["per_hop_payload_bytes"]
        assert ladder is not None, qname
        # the travelling shard halves as the ring widens
        assert ladder["tp=2"] == 2 * ladder["tp=4"] == \
            4 * ladder["tp=8"], (qname, ladder)


def test_seams_ride_the_tp_programs(manifest):
    progs = manifest["programs"]
    bodies = {p["body"] for p in progs.values()}
    assert {"paddle_tpu.serving.tp._tp_decode_body",
            "paddle_tpu.serving.tp._tp_verify_body"} <= bodies
    attributed = manifest["seams"][ENTRY_COMPOSED]["programs"]
    assert {e["uid"] for e in attributed} >= {
        uid for uid, p in progs.items()
        if p["body"] == "paddle_tpu.serving.tp._tp_decode_body"}


# ------------------------------------- cache invalidation fingerprint

def test_comm_fingerprint_joins_cache_version():
    from paddle_tpu.tools.analysis.walker import _cache_version
    assert comm_fingerprint() in _cache_version()


def test_stale_cache_not_served_after_comm_module_change(tmp_path):
    """End-to-end: a saved parse cache is NOT loaded once the comm
    module table differs from the one it was written under."""
    from paddle_tpu.tools.analysis import register_comm_module
    from paddle_tpu.tools.analysis.comm import _EXTRA_COMM_MODULES
    from paddle_tpu.tools.analysis.walker import (_ParseCache,
                                                  _parse_files)
    f = tmp_path / "m.py"
    f.write_text("x = 1\n")
    cache_path = str(tmp_path / "cache.pkl")
    c1 = _ParseCache(cache_path)
    _parse_files([str(f)], str(tmp_path), c1)
    c1.save()
    assert _ParseCache(cache_path).entries    # same tables: served
    register_comm_module("zz.stale.comm_probe")
    try:
        assert not _ParseCache(cache_path).entries   # stale: dropped
    finally:
        _EXTRA_COMM_MODULES.remove("zz.stale.comm_probe")
    assert _ParseCache(cache_path).entries    # tables restored: served
