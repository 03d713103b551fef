"""A DROPLESS expert layer: every token reaches every expert it chose.

``distributed/moe.py::MoELayer`` dispatches through one-hot ``[tokens,
experts, capacity]`` einsums and drops what overflows an expert's
capacity.  Here nothing is capped: the ``tokens x top_k`` assignments are
sorted by expert, the rows each expert got are counted, and ONE grouped
matmul over the ragged groups computes every expert's SiLU-gated MLP
(gate and up, the gate, down); the results are weighted and summed back
per token, and a shared expert (a plain gated MLP every token passes)
is added beside them.

Routing is DeepSeek-V3's (arXiv:2412.19437, ``noaux_tc`` with one
group): ``s = sigmoid(W_g u)`` in float32 on the float32 input; the
``top_k`` experts with the largest ``s + b`` are CHOSEN (``b`` the
``e_score_correction_bias``, used for the choice only); ``w_e =
scale * s_e / (sum of the chosen s + 1e-20)``.

A row that is not LIVE (a parked serving slot, the padding of a prefill
chunk) is routed to no expert: its assignments sort behind every real
group, no group counts them and the grouped matmul never reads an
expert's weights on their behalf.  That matters where the experts are
most of the model's bytes: a decode step over 8 live slots of 32 reads
the experts those 8 chose, not those 32 would have.

The grouped matmul has two forms, picked by :func:`grouped_matmul_route`
from the platform and the shapes (no knob): the Pallas ``megablox.gmm``
kernel on a TPU, whose grid visits only the groups that hold rows, and
``jax.lax.ragged_dot`` elsewhere.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, ParamAttr
from ..nn.layers.common import Linear

__all__ = ["DroplessMoE", "GatedMLP", "sigmoid_topk_route",
           "grouped_matmul", "grouped_matmul_route", "sort_by_expert",
           "GMM_ROW_TILE"]

# rows of the sorted assignments one grid step of the kernel takes.  A
# step multiplies a whole tile by ONE expert's weights and masks the rows
# of its neighbours, so the tile follows the rows an expert gets: 16-64
# in a prefill chunk of 512-2048 tokens at 256 experts top-8 (and the
# kernel's output tile is float32 [tile, n]), one or two in a decode step
GMM_ROW_TILE = {"chunk": 128, "step": 16}
# a lhs of at most this many rows is a decode step's
_STEP_ROWS = 512


def sigmoid_topk_route(logits, bias, top_k: int, scale: float,
                       normalize: bool = True):
    """``logits [t, experts]`` float32 -> ``(experts chosen [t, top_k]
    int32, weights [t, top_k] float32)``."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def sort_by_expert(idx, live, num_experts: int):
    """The ``t x top_k`` assignments ``idx [t, top_k]`` in expert order.
    ``live [t]`` bool: the rows that are routed at all.  Returns
    ``(order [t * top_k], group_sizes [experts], inverse [t * top_k])``:
    sorted assignment ``j`` is token ``order[j] // top_k``; the
    assignments of rows that are not live sort last and are counted in
    no group; ``inverse`` undoes the sort."""
    flat = jnp.where(live[:, None], idx, num_experts).reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    group_sizes = jnp.zeros((num_experts + 1,), jnp.int32).at[flat].add(
        1)[:num_experts]
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    return order, group_sizes, inverse


def grouped_matmul_route(m: int, k: int, n: int, dtype):
    """``(route, reason)`` of the grouped matmul of ``[m, k]`` sorted
    rows with ``[groups, k, n]`` weights, traced HERE: ``("gmm", None)``
    (the Pallas kernel, a TPU and shapes it tiles) or ``("ragged_dot",
    why)``.  Static per compiled program."""
    if jax.default_backend() != "tpu":
        return "ragged_dot", "no TPU: the Pallas grouped matmul is a " \
                             "Mosaic kernel"
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return "ragged_dot", f"dtype {jnp.dtype(dtype).name}"
    tile = _row_tile(m)
    if m % tile or k % 128 or n % 128:
        return "ragged_dot", (f"shape: m {m} not a multiple of the row "
                              f"tile {tile}, or k {k} / n {n} not of 128")
    return "gmm", None


def _row_tile(m: int) -> int:
    return GMM_ROW_TILE["step" if m <= _STEP_ROWS else "chunk"]


def gmm_form(lhs, rhs, group_sizes, row_tile: Optional[int] = None,
             interpret: bool = False):
    """``megablox.gmm`` with one expert's WHOLE ``[k, n]`` matrix a grid
    step (at these widths 3.1 MB in bfloat16: a step's DMA is long
    against its fixed cost, and no expert's weights are fetched twice).
    Rows past ``sum(group_sizes)`` are left unwritten."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = lhs.shape
    n = rhs.shape[2]
    # the kernel's products at the operands' own precision, whatever the
    # process's default (Mosaic refuses a float32-precision product of
    # bfloat16 operands)
    with jax.default_matmul_precision("default"):
        return gmm(lhs, rhs, group_sizes,
                   preferred_element_type=jnp.float32,
                   tiling=(row_tile or _row_tile(m), k, n),
                   interpret=interpret)


def ragged_form(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


def grouped_matmul(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``
    (rows sorted by group, ``group_sizes [groups]``), float32.  Rows
    past the last group hold nothing a caller may use."""
    route, _ = grouped_matmul_route(lhs.shape[0], lhs.shape[1],
                                    rhs.shape[2], lhs.dtype)
    if route == "gmm":
        return gmm_form(lhs, rhs, group_sizes)
    return ragged_form(lhs, rhs, group_sizes)


class _BlockedNormal(I.Initializer):
    """``normal(0, std)`` for a ``[experts, ...]`` stack, drawn one
    expert at a time: the float32 draw of a whole ``[256, 2048, 768]``
    stack is 1.6 GB before its cast, and a model has a dozen."""

    def __init__(self, std: float):
        self.std = std

    def init(self, key, shape, dtype):
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(
            lambda k: (self.std * jax.random.normal(
                k, shape[1:], jnp.float32)).astype(dtype), keys)


class GatedMLP(Layer):
    """``down(silu(gate u) * up u)`` with the gate and the branch's
    output left in their matmuls' float32 accumulators (models/jamba.py
    says why): the shared expert here, a dense layer's feed-forward in
    models/deepseek_v3.py."""

    def __init__(self, hidden_size: int, size: int, init_std: float = 0.02):
        super().__init__()
        for name, rows, cols in (("gate_proj", hidden_size, size),
                                 ("up_proj", hidden_size, size),
                                 ("down_proj", size, hidden_size)):
            setattr(self, name, Linear(
                rows, cols, bias_attr=False, weight_attr=ParamAttr(
                    initializer=I.Normal(0.0, init_std))))

    def forward(self, u):
        """``u [t, hidden]`` in the weights' dtype -> float32."""
        a = F.silu(_wide(self.gate_proj, u)) * _wide(self.up_proj, u)
        return _wide(self.down_proj, a.astype(u.dtype))


class DroplessMoE(Layer):
    """``out = sum over the chosen of w_e E_e(u) + E_shared(u)``, each
    ``E`` a SiLU-gated MLP of width ``expert_size``.

    Parameters: ``gate.weight [hidden, experts]`` (the router),
    ``e_score_correction_bias [experts]``, the routed experts stacked
    ``gate_proj / up_proj [experts, hidden, expert_size]`` and
    ``down_proj [experts, expert_size, hidden]``, and
    ``shared_experts`` (``n_shared`` experts as ONE :class:`GatedMLP` of
    width ``n_shared x expert_size``, as published)."""

    def __init__(self, hidden_size: int, expert_size: int,
                 num_experts: int, top_k: int, n_shared: int = 1,
                 routed_scale: float = 1.0, normalize: bool = True,
                 init_std: float = 0.02,
                 routed_out_std: Optional[float] = None):
        super().__init__()
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} > num_experts {num_experts}")
        self.hidden_size, self.expert_size = hidden_size, expert_size
        self.num_experts, self.top_k = num_experts, top_k
        self.routed_scale, self.normalize = routed_scale, normalize
        # ``routed_out_std``: the routed experts' down projections
        # (None: as every other matrix)
        routed_out_std = init_std if routed_out_std is None \
            else routed_out_std
        normal = ParamAttr(initializer=I.Normal(0.0, init_std))
        stack = ParamAttr(initializer=_BlockedNormal(init_std))
        self.gate = Linear(hidden_size, num_experts, weight_attr=normal,
                           bias_attr=False)
        self.e_score_correction_bias = self.create_parameter(
            (num_experts,), default_initializer=I.Constant(0.0))
        self.gate_proj = self.create_parameter(
            (num_experts, hidden_size, expert_size), attr=stack)
        self.up_proj = self.create_parameter(
            (num_experts, hidden_size, expert_size), attr=stack)
        self.down_proj = self.create_parameter(
            (num_experts, expert_size, hidden_size),
            attr=ParamAttr(initializer=_BlockedNormal(routed_out_std)))
        self.n_shared = n_shared
        if n_shared:
            self.shared_experts = GatedMLP(
                hidden_size, n_shared * expert_size, init_std)

    def route(self, u32):
        """``u32 [t, hidden]`` float32 -> ``(chosen, weights)``; the
        scores are a float32 product whatever the platform's default
        matmul precision."""
        logits = jnp.dot(u32, self.gate.weight.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        return sigmoid_topk_route(logits, self.e_score_correction_bias,
                                  self.top_k, self.routed_scale,
                                  self.normalize)

    def forward(self, u32, live=None):
        """``u32 [t, hidden]`` float32 (the normed input); ``live [t]``
        bool (None: all).  Returns ``(out [t, hidden] float32, rows
        [experts] int32)``, ``rows`` the live rows each expert got."""
        t, h = u32.shape
        dt = self.gate_proj.dtype
        if live is None:
            live = jnp.ones((t,), bool)
        # the three parts of an expert layer (obs/parts.py): the scores,
        # the choice and the sort; the routed experts from the gather to
        # the weighted sum; the shared expert and its add
        with jax.named_scope("router"):
            idx, w = self.route(u32)
            order, rows, inverse = sort_by_expert(idx, live,
                                                  self.num_experts)
        u = u32.astype(dt)
        with jax.named_scope("experts"):
            x = u[order // self.top_k]                   # [t * k, h]
            a = (F.silu(grouped_matmul(x, self.gate_proj, rows))
                 * grouped_matmul(x, self.up_proj, rows)).astype(dt)
            y = grouped_matmul(a, self.down_proj, rows)  # [t * k, h] f32
            y = y[inverse].reshape(t, self.top_k, h)
            # a row that is not live was computed by no expert: select,
            # do not multiply (what the kernel left there is not a number)
            y = jnp.where(live[:, None, None], y, 0.0)
            out = jnp.sum(y * w[..., None], axis=1)
        if self.n_shared:
            with jax.named_scope("shared_expert"):
                out = out + self.shared_experts(u)
        return out, rows


def _wide(layer: Linear, x):
    """``layer(x)`` left in the matmul's float32 accumulator."""
    return jnp.dot(x, layer.weight, preferred_element_type=jnp.float32)
