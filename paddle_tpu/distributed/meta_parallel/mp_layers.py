"""Tensor-parallel (Megatron-style) layers.

Reference: python/paddle/distributed/fleet/meta_parallel/parallel_layers/
mp_layers.py — ColumnParallelLinear, RowParallelLinear,
VocabParallelEmbedding, ParallelCrossEntropy (backed by
c_softmax_with_cross_entropy CUDA op and identity-fwd/allreduce-bwd
PyLayers).

TPU-native: the layers hold FULL (global-shape) weights annotated with
PartitionSpecs over the ``mp`` mesh axis; forward is the plain math plus
``with_sharding_constraint`` on activations.  XLA GSPMD partitions the
matmuls and inserts the all-reduce/all-gather the reference hand-writes
(mp_ops._IdentityInFwdAllReduceInBwd etc.).  API (gather_output,
input_is_parallel, has_bias, mp_group) matches the reference so fleet
scripts port unchanged.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ..topology import get_hybrid_communicate_group
from ..sharding_utils import set_param_spec

__all__ = ["ColumnParallelLinear", "RowParallelLinear",
           "VocabParallelEmbedding", "ParallelCrossEntropy",
           "parallel_cross_entropy"]


def _mp_axis(mp_group) -> str:
    if mp_group is not None and hasattr(mp_group, "name"):
        return mp_group.name
    return "mp"


def _maybe_constraint(x, spec: P):
    """Apply a sharding constraint when running under jit with a mesh in
    scope; harmless no-op in plain eager."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


class ColumnParallelLinear(Layer):
    """Y = X W, W [in, out] split along out (columns).  Output stays
    mp-sharded when gather_output=False (feeding RowParallelLinear)."""

    def __init__(self, in_features: int, out_features: int,
                 weight_attr=None, has_bias: bool = True,
                 gather_output: bool = True, fuse_matmul_bias: bool = False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self._axis = _mp_axis(mp_group)
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        set_param_spec(self, "weight", P(None, self._axis))
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            set_param_spec(self, "bias", P(self._axis))
        else:
            self.add_parameter("bias", None)

    def forward(self, x):
        y = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            y = _maybe_constraint(y, P(*([None] * y.ndim)))
        else:
            y = _maybe_constraint(y, P(*([None] * (y.ndim - 1)), self._axis))
        return y


class RowParallelLinear(Layer):
    """Y = X W, W [in, out] split along in (rows).  Input is expected
    mp-sharded on the last dim when input_is_parallel=True; the partial
    products are all-reduced (by GSPMD) into a replicated output."""

    def __init__(self, in_features: int, out_features: int,
                 weight_attr=None, has_bias: bool = True,
                 input_is_parallel: bool = False, fuse_matmul_bias: bool = False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self._axis = _mp_axis(mp_group)
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal())
        set_param_spec(self, "weight", P(self._axis, None))
        if has_bias:
            # bias added after the reduction -> replicated (reference: bias
            # added post-allreduce on rank path)
            self.bias = self.create_parameter((out_features,), is_bias=True)
            set_param_spec(self, "bias", P())
        else:
            self.add_parameter("bias", None)

    def forward(self, x):
        if self.input_is_parallel:
            x = _maybe_constraint(x, P(*([None] * (x.ndim - 1)), self._axis))
        y = jnp.matmul(x, self.weight)
        y = _maybe_constraint(y, P(*([None] * y.ndim)))
        if self.bias is not None:
            y = y + self.bias
        return y


class VocabParallelEmbedding(Layer):
    """Embedding table split along vocab.  GSPMD turns the gather into a
    partial lookup + all-reduce (reference: masked local lookup + allreduce
    in mp_ops)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self._axis = _mp_axis(mp_group)
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.Normal(0.0, 0.02))
        set_param_spec(self, "weight", P(self._axis, None))

    def forward(self, x):
        out = jnp.take(self.weight, x.astype(jnp.int32), axis=0)
        return _maybe_constraint(out, P(*([None] * (x.ndim + 1))))


def _take_rows_f32grad(table, ids):
    """take(table, ids, axis=0) whose bwd scatter-add runs in f32.

    XLA's SPMD partitioner CHECK-fails partitioning a bf16 scatter-add
    in modules that also contain a pipeline shard_map (the operand-
    upcaster's convert pattern trips the b/433785288 involuntary-remat
    path — round-5 notes; the identical f32 program compiles).  Doing
    the accumulation in f32 ourselves sidesteps the upcaster AND is the
    numerically better program: embedding-row grads accumulate many
    updates, exactly what multi_precision masters exist for."""
    import numpy as _np
    shape, dt = table.shape, table.dtype

    @jax.custom_vjp
    def tk(t, i):
        return jnp.take(t, i, axis=0)

    def fwd(t, i):
        return jnp.take(t, i, axis=0), i

    def bwd(i, g):
        gt = jnp.zeros(shape, jnp.float32).at[i].add(
            g.astype(jnp.float32))
        return (gt.astype(dt),
                _np.zeros(i.shape, jax.dtypes.float0))

    tk.defvjp(fwd, bwd)
    return tk(table, ids.astype(jnp.int32))


def sharded_row_take(table, ids, row_axes, mesh):
    """``jnp.take(table, ids, axis=0)`` for a table whose ROW dim is
    sharded over mesh axes ``row_axes`` — as an explicit Megatron-style
    masked local lookup + psum inside a partial-manual shard_map
    (reference: VocabParallelEmbedding's range mask + allreduce in
    mp_ops.py).

    The manual form never shows the partitioner a sharded scatter: the
    bwd is a local dense scatter + the psum transpose, and the mask+psum
    is one fused elementwise over the lookup result.  Suitable for
    single-group row shardings (e.g. a vocab table over mp); NOTE: in
    hybrid meshes where OTHER auto axes shard the indices AND the row
    axes carry subgroup structure (the pp-extended tables of the hybrid
    trainer), XLA's partitioner fails a psum replica-group CHECK
    (spmd_partitioner_util.cc:495) — the trainer therefore uses
    _take_rows_f32grad (GSPMD gather with f32 scatter-accumulate bwd),
    which compiles on every tested hybrid config (round-5 notes).

    Falls back to the GSPMD-gather form when the rows don't divide
    evenly over the axes (shard_map needs exact tiling)."""
    axes = ((row_axes,) if isinstance(row_axes, str)
            else tuple(row_axes))
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    if table.shape[0] % n_shards:
        return _take_rows_f32grad(table, ids)
    from .._jax_compat import shard_map

    def body(tbl, ids_):
        lin = 0
        for ax in axes:
            lin = lin * mesh.shape[ax] + jax.lax.axis_index(ax)
        v_local = tbl.shape[0]
        local = ids_ - lin * v_local
        ok = (local >= 0) & (local < v_local)
        out = _take_rows_f32grad(tbl, jnp.clip(local, 0, v_local - 1))
        out = jnp.where(ok[..., None], out, jnp.zeros((), tbl.dtype))
        # psum in f32: shardy's HLO round-trip corrupts BF16 reduction
        # combiners (copy-rooted add), which later XLA passes CHECK-fail
        # on — and the f32 accumulation is numerically right anyway
        return jax.lax.psum(out.astype(jnp.float32), axes).astype(
            tbl.dtype)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axes if len(axes) > 1 else axes[0], None), P()),
        out_specs=P(), check_vma=False,
        axis_names=set(axes))(table, ids.astype(jnp.int32))


def parallel_cross_entropy(logits, label, ignore_index: int = -100,
                           mp_axis: str = "mp"):
    """Vocab-parallel softmax cross-entropy.

    Reference: paddle/fluid/operators/collective/
    c_softmax_with_cross_entropy_op.cu — per-shard max/sum with two
    allreduces, never materializing the full softmax.  Under GSPMD we write
    the stable logsumexp on (constraint-)sharded logits; XLA performs the
    reductions over the sharded vocab axis with exactly those collectives.
    """
    vocab_sharded = P(*([None] * (logits.ndim - 1)), mp_axis)
    with jax.named_scope("loss"):
        logits = _maybe_constraint(logits, vocab_sharded)
        x = logits.astype(jnp.float32)
        m = jnp.max(x, axis=-1, keepdims=True)
        lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
        lbl = label.astype(jnp.int32)
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0)
        picked = jnp.take_along_axis(x, safe[..., None], axis=-1)
        loss = (lse - picked)[..., 0]
        return jnp.where(valid, loss, 0.0)


class ParallelCrossEntropy(Layer):
    def __init__(self, mp_group=None, name=None, ignore_index: int = -100):
        super().__init__()
        self._axis = _mp_axis(mp_group)
        self.ignore_index = ignore_index

    def forward(self, input, label):
        return parallel_cross_entropy(input, label, self.ignore_index,
                                      self._axis)


# --- paddle.distributed.split (OP_COVERAGE round 3) ----------------------

_SPLIT_CACHE: dict = {}


def split(x, size, operation: str = "linear", axis: int = 0,
          num_partitions: int = 1, gather_out: bool = True,
          weight_attr=None, bias_attr=None, name=None):
    """Megatron-style parallel op factory (reference:
    paddle.distributed.split): builds a column/row-parallel Linear or a
    vocab-parallel Embedding over the mp axis and applies it.

    Porting shim semantics: the underlying layer (and its parameters) is
    CREATED ON FIRST CALL and cached under the REQUIRED ``name`` — two
    unnamed call sites with the same shapes must not silently share
    weights, so ``name`` is mandatory here (the reference's static-graph
    unique-naming plays that role upstream).  Training code should prefer
    the explicit ColumnParallelLinear/RowParallelLinear/
    VocabParallelEmbedding layers.  The cache clears on
    destroy_process_group (layers bake the mesh of the topology they were
    built under)."""
    if name is None:
        raise ValueError(
            "distributed.split needs an explicit name= (it caches the "
            "created parallel layer; unnamed call sites with equal shapes "
            "would silently share parameters)")
    hcg = get_hybrid_communicate_group()
    if hcg is not None and num_partitions not in (
            1, hcg.get_model_parallel_world_size()):
        raise ValueError(
            f"num_partitions={num_partitions} does not match the "
            f"initialized mp degree "
            f"{hcg.get_model_parallel_world_size()} (reference validates "
            f"the same)")
    key = name
    cfg = (operation, tuple(size), axis)
    cached = _SPLIT_CACHE.get(key)
    if cached is not None and cached[1] != cfg:
        raise ValueError(
            f"distributed.split name {name!r} was first used with config "
            f"{cached[1]}, now called with {cfg}; one name = one layer")
    layer = cached[0] if cached is not None else None
    if layer is None:
        if operation == "linear":
            in_f, out_f = size
            if axis == 1:
                layer = ColumnParallelLinear(
                    in_f, out_f, weight_attr=weight_attr,
                    has_bias=bias_attr is not False,
                    gather_output=gather_out)
            else:
                layer = RowParallelLinear(
                    in_f, out_f, weight_attr=weight_attr,
                    has_bias=bias_attr is not False,
                    input_is_parallel=False)
        elif operation == "embedding":
            num_emb, emb_dim = size
            layer = VocabParallelEmbedding(num_emb, emb_dim,
                                           weight_attr=weight_attr)
        else:
            raise ValueError(f"unknown split operation {operation!r}")
        _SPLIT_CACHE[key] = (layer, cfg)
    return layer(x)
