"""Distributed inference: model.generate and beam_search on a
TP(mp)-sharded model over the virtual mesh — GSPMD partitions the whole
compiled decode scan; outputs must match the dense single-device run
token for token (greedy decoding is float-sensitive only at near-ties,
so the oracle compares SCORES with tolerance and sequences exactly under
matched arithmetic where possible)."""

import numpy as np
import pytest


import jax
import jax.numpy as jnp

import paddle_tpu
import paddle_tpu.distributed as dist
from paddle_tpu.models import LlamaForCausalLM, llama_shard_fn, llama_tiny
from paddle_tpu.models.generation import beam_search


def _build(shard):
    paddle_tpu.seed(11)
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    if shard:
        mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4),
                                dim_names=["dp", "mp"])
        dist.shard_layer(model, mesh, llama_shard_fn(mesh))
    return model


def test_generate_on_mp_sharded_model_matches_dense():
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (4, 6)))
    dense = _build(False)
    seq_d, sc_d = dense.generate(ids, max_new_tokens=5, output_scores=True)
    sharded = _build(True)
    seq_s, sc_s = sharded.generate(ids, max_new_tokens=5,
                                   output_scores=True)
    # scores: same function, different partitioning -> tolerance
    np.testing.assert_allclose(np.asarray(sc_s), np.asarray(sc_d),
                               rtol=2e-4, atol=2e-4)
    # greedy chains agree unless a near-tie flips a token; verify each
    # sharded token is (near-)argmax under the dense scores
    sd = np.asarray(sc_d)
    toks = np.asarray(seq_s)[:, 6:]
    for bi in range(toks.shape[0]):
        for t in range(toks.shape[1]):
            chosen = sd[bi, t, toks[bi, t]]
            best = sd[bi, t].max()
            assert best - chosen < 1e-3, (bi, t, best - chosen)


def test_beam_search_on_mp_sharded_model():
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 5)))
    dense = _build(False)
    seq_d, score_d = beam_search(dense, ids, max_new_tokens=4, beam_size=3)
    sharded = _build(True)
    seq_s, score_s = beam_search(sharded, ids, max_new_tokens=4,
                                 beam_size=3)
    np.testing.assert_allclose(np.asarray(score_s), np.asarray(score_d),
                               rtol=2e-3, atol=2e-3)
    assert seq_s.shape == seq_d.shape
