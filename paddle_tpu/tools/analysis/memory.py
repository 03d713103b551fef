"""graftmem — static HBM/VMEM byte accounting (analysis v5).

graftprog (v4) proved the serving stack's *program-set* pin; graftmem
proves its *memory* pin.  Riding the graftshape domain (an array's
bytes are ``prod(shape) * dtype_width/8`` with symbolic extents kept as
named capacity fields), it derives — without importing anything:

  * **pool footprints** — every ``*Pool`` class's device slabs, read
    straight out of the constructor AST (the ``shape = (...)`` local,
    the per-layer listcomp allocation, the direct vector allocs), as a
    closed-form byte FORMULA over registered capacity fields
    (``num_slots``, ``max_seq``, ``num_blocks``, ...) plus the
    symbolic ``itemsize``;
  * **VMEM working sets** — faithful integer mirrors of the Pallas
    tiling plans (``plan_decode_block`` / ``plan_decode_block_tp``)
    re-derive each plan's per-grid-step residents over the reference
    tilings and check them against the budget the kernel module
    DECLARES (``VMEM_BUDGET``, folded from its AST, resolved through
    imports).  A mirror-fidelity test (tests/test_zz_memory_surface.py)
    pins the mirrors to the live plan functions, so plan drift cannot
    silently de-sync the static check;
  * **per-program peak residents** — for each compile unit on the
    graftprog manifest's counter planes, an evidence-legged estimate
    (weights + slabs + staging + row state + activations at the widest
    bucket), donation-aware: a donated slab is updated in place and
    counts ONCE, an undonated slab pays input + output;
  * **the HBM capacity manifest** — ``scripts/graftlint.py --memory``:
    per-pool bytes-per-block at {bf16, int8}, the derived
    max-resident-blocks ladder per chip HBM size (ROADMAP direction
    3's build input), and the ``EngineCore`` plane's fixed-footprint
    proof (every persistent device allocation sits in an
    init/rebuild-owned constructor — nothing allocates after warmup).

The ``memory-budget`` rule (checkers/memory_budget.py) turns the same
facts into findings; :func:`memory_fingerprint` folds the registries
and reference tilings into the walker's parse-cache version so a
runtime registration never serves stale analysis state.

Like every graftlint pass this module is pure AST + integer
arithmetic: no jax, no imports of the code under analysis.
"""

from __future__ import annotations

import ast
import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .absint import dtype_width

__all__ = [
    "GRAFTMEM_VERSION", "CAPACITY_DUNDER", "VMEM_PLANS_DUNDER",
    "MEMORY_BYTES_DUNDER", "CHIP_HBM_BYTES", "DEFAULT_VMEM_BUDGET",
    "DEFAULT_CAPACITY_FIELDS", "REFERENCE_ENV", "REFERENCE_TILINGS",
    "PLAN_MIRRORS", "register_capacity_field",
    "registered_capacity_fields", "register_byte_signature",
    "registered_byte_signatures", "memory_fingerprint", "eval_formula",
    "itemsize_bytes", "mirror_plan_decode_block",
    "mirror_plan_decode_block_tp", "memory_surface_for",
    "build_memory_manifest", "build_memory_manifest_for_paths",
]

GRAFTMEM_VERSION = 1

# in-source markers (read from the AST, zero runtime cost):
#   __memory_capacity_fields__ = ("ring_depth",)     extra capacity names
#   __vmem_plans__ = ("plan_decode_block",)          plans this module owns
#   __memory_bytes__ = {"staging": "2 * num_layers * ..."}   declared legs
CAPACITY_DUNDER = "__memory_capacity_fields__"
VMEM_PLANS_DUNDER = "__vmem_plans__"
MEMORY_BYTES_DUNDER = "__memory_bytes__"

# per-chip HBM for the max-resident-blocks ladder
CHIP_HBM_BYTES = {
    "v4": 32 * 1024**3,
    "v5e": 16 * 1024**3,
    "v5p": 95 * 1024**3,
    "v6e": 32 * 1024**3,
}

# mirror of kernels/decode_block.py VMEM_BUDGET — the fallback when a
# plan-declaring module's own constant cannot be folded from its AST
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024

# ----------------------------------------------------------- registries

# shape extents a fixed-footprint pool allocation is allowed to flow
# from: the engine/pool constructor capacity parameters.  Extend per
# module with the CAPACITY_DUNDER marker or register_capacity_field().
DEFAULT_CAPACITY_FIELDS = frozenset({
    "num_slots", "max_seq", "num_layers", "kv_heads", "head_dim",
    "num_blocks", "block_len", "blocks_per_row", "num_heads", "hidden",
    "vocab_size", "ffn", "itemsize", "spec_k",
    # bytes of recurrent state a slot holds beside its KV rows (what the
    # model declares in ``recurrent_state_spec``; 0 for most)
    "state_bytes_per_slot",
    # the pool's V slabs: ``num_layers`` of them, or 0 where a cached
    # position holds ONE row kind (a latent row: kv_pool.cache_row)
    "v_slabs",
})
_EXTRA_CAPACITY_FIELDS: List[str] = []

# byte semantics of the allocator calls the pool walk recognizes:
# qname -> cost formula (documentation + fingerprint payload; the walk
# matches on the leaf name)
DEFAULT_BYTE_SIGNATURES: Dict[str, str] = {
    "jnp.zeros": "prod(shape) * itemsize",
    "jnp.ones": "prod(shape) * itemsize",
    "jnp.full": "prod(shape) * itemsize",
    "jnp.empty": "prod(shape) * itemsize",
}
_EXTRA_BYTE_SIGNATURES: Dict[str, str] = {}


def register_capacity_field(name: str) -> None:
    """Register an extra capacity-field name (tests, downstream pools)
    in addition to :data:`DEFAULT_CAPACITY_FIELDS`."""
    if name not in _EXTRA_CAPACITY_FIELDS:
        _EXTRA_CAPACITY_FIELDS.append(name)


def registered_capacity_fields() -> frozenset:
    return DEFAULT_CAPACITY_FIELDS | frozenset(_EXTRA_CAPACITY_FIELDS)


def register_byte_signature(qname: str, formula: str) -> None:
    """Register an allocator's byte semantics (``pkg.alloc`` ->
    formula).  The leaf name joins the pool walk's allocator set and
    the registration participates in the parse-cache fingerprint."""
    _EXTRA_BYTE_SIGNATURES[qname] = formula


def registered_byte_signatures() -> Dict[str, str]:
    out = dict(DEFAULT_BYTE_SIGNATURES)
    out.update(_EXTRA_BYTE_SIGNATURES)
    return out


def _allocator_leaves() -> frozenset:
    return frozenset(q.rsplit(".", 1)[-1]
                     for q in registered_byte_signatures())


def memory_fingerprint() -> str:
    """Stable content hash of the byte-accounting configuration — rule
    version, registered byte signatures, capacity fields, reference
    tilings and the default budget.  Part of the walker's parse-cache
    version: registering a signature or budget must never serve
    analysis state derived under the old tables."""
    sigs = ",".join(f"{k}={v}" for k, v in
                    sorted(registered_byte_signatures().items()))
    tilings = ";".join(
        f"{t['name']}:{t['plan']}:" + ",".join(
            f"{k}={v}" for k, v in sorted(t["kwargs"].items()))
        for t in REFERENCE_TILINGS)
    payload = "|".join((str(GRAFTMEM_VERSION), sigs,
                        ",".join(sorted(registered_capacity_fields())),
                        tilings, str(DEFAULT_VMEM_BUDGET)))
    return hashlib.sha1(payload.encode("utf-8")).hexdigest()


# ------------------------------------------------------ byte arithmetic

def itemsize_bytes(dtype: Optional[str]) -> Optional[int]:
    """graftshape dtype name -> element bytes (bool packs to one)."""
    w = dtype_width(dtype)
    if w is None:
        return None
    return max(1, w // 8)


class FormulaError(ValueError):
    pass


def _eval_node(node: ast.AST, env: Dict[str, int]):
    if isinstance(node, ast.Constant) and isinstance(node.value,
                                                    (int, float)):
        return node.value
    if isinstance(node, ast.Name):
        if node.id not in env:
            raise FormulaError(f"unbound capacity field '{node.id}'")
        return env[node.id]
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv)):
        a = _eval_node(node.left, env)
        b = _eval_node(node.right, env)
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        if isinstance(node.op, ast.FloorDiv):
            return a // b
        return a / b
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval_node(node.operand, env)
    raise FormulaError(
        f"unsupported construct in byte formula: {ast.dump(node)}")


def eval_formula(formula: str, env: Dict[str, int]) -> int:
    """Evaluate a byte formula (names, ints, ``+ - * / //``) under a
    capacity environment.  Raises :class:`FormulaError` on anything
    else — formulas are data, not code."""
    try:
        tree = ast.parse(formula, mode="eval")
    except SyntaxError as e:
        raise FormulaError(f"bad byte formula {formula!r}: {e}") from e
    return int(round(_eval_node(tree.body, env)))


def _fold_int(node: ast.AST) -> Optional[int]:
    """Fold a compile-time int expression (``12 * 1024 * 1024``)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.Add, ast.Sub, ast.Mult, ast.FloorDiv)):
        a, b = _fold_int(node.left), _fold_int(node.right)
        if a is None or b is None:
            return None
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        return a // b if b else None
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _fold_int(node.operand)
        return None if v is None else -v
    return None


# ----------------------------------------------------- the plan mirrors
#
# Faithful integer transcriptions of the Pallas VMEM plans.  They MUST
# stay line-for-line equivalent to kernels/decode_block.py and
# kernels/decode_block_tp.py — tests/test_zz_memory_surface.py compares
# mirror output to live plan output over every reference tiling, so a
# drifted mirror fails loudly rather than silently mis-budgeting.

# mirror of kernels/decode_block.py ATTN_CHUNK
_ATTN_CHUNK = 16


def _mirror_fit_tile(dim: int, per_unit: int, fixed: int, budget: int):
    lane = [t for t in range(128, dim + 1, 128) if dim % t == 0]
    for t in sorted(lane, reverse=True):
        if fixed + per_unit * t <= budget:
            return t
    for t in sorted((t for t in range(1, dim + 1) if dim % t == 0),
                    reverse=True):
        if fixed + per_unit * t <= budget:
            return t
    return None


def _mirror_slab_row_bytes(kv_heads: int, head_dim: int, itemsize: int):
    lanes = -(-head_dim // 128) * 128
    sub = 8 * (4 // itemsize)
    return (-(-kv_heads // sub) * sub * lanes * itemsize,
            -(-kv_heads // 8) * 8 * lanes * 4)


def _mirror_plan_slab_attention(max_seq: int, kv_heads: int, rep: int,
                                head_dim: int, itemsize: int,
                                vmem_budget: int):
    row, row32 = _mirror_slab_row_bytes(kv_heads, head_dim, itemsize)
    fixed = (4 * min(_ATTN_CHUNK, max_seq) * row32
             + (2 * rep + 4) * row32 + 2 * row
             + 3 * head_dim * max(head_dim, 128) * 4)
    bk = min(1024, max_seq)
    while max_seq % bk:
        bk //= 2
    while bk > 8 and fixed + 4 * bk * row > vmem_budget:
        bk //= 2
    need = fixed + 4 * bk * row
    return (bk if need <= vmem_budget else None), need


def mirror_plan_decode_block(*, max_seq: int, hidden: int, heads: int,
                             kv_heads: int, head_dim: int, ffn: int,
                             batch: int, itemsize: int,
                             gated: bool = False,
                             vmem_budget: int = DEFAULT_VMEM_BUDGET):
    """Mirror of ``kernels.decode_block.plan_decode_block`` (tp=1)."""
    rep = heads // kv_heads
    dh = head_dim
    bk, vmem_attn = _mirror_plan_slab_attention(
        max_seq, kv_heads, rep, dh, itemsize, vmem_budget)
    if bk is None:
        return None, (f"vmem: attention residents {vmem_attn} bytes exceed "
                      f"budget {vmem_budget} even at block_k=8")
    proj_fixed = batch * hidden * 2 * itemsize + 2 * hidden * 4
    proj_unit = 2 * (hidden * itemsize + itemsize + batch * 4)
    bn = _mirror_fit_tile(kv_heads * dh, proj_unit, proj_fixed,
                          vmem_budget)
    if bn is None:
        return None, (f"vmem: projection residents {proj_fixed} bytes + "
                      f"weight tiles exceed budget {vmem_budget} at any "
                      f"tile of the K/V width {kv_heads * dh}")
    mlp_fixed = (batch * hidden * 2 * itemsize
                 + batch * hidden * (8 + itemsize)
                 + 4 * hidden * 4)
    o_unit = 2 * (hidden + batch) * itemsize
    n_mats = 3 if gated else 2
    f_unit = 2 * (n_mats * hidden + 1) * itemsize
    bo = bf = None
    cands = [f for f in range(128, ffn + 1, 128) if ffn % f == 0] or [ffn]
    for c in sorted(cands, reverse=True):
        bo = _mirror_fit_tile(heads * dh, o_unit, mlp_fixed + f_unit * c,
                              vmem_budget)
        if bo is not None:
            bf = c
            break
    if bf is None:
        need = mlp_fixed + f_unit * min(cands) + o_unit
        return None, (f"vmem: proj+MLP residents {need} bytes exceed "
                      f"budget {vmem_budget} even at block_f={min(cands)}")
    return {"block_k": bk, "block_n": bn, "block_o": bo, "block_f": bf,
            "vmem_attn": vmem_attn,
            "vmem_proj": proj_fixed + proj_unit * bn,
            "vmem_mlp": mlp_fixed + o_unit * bo + f_unit * bf}, None


def mirror_plan_decode_block_tp(*, max_seq: int, hidden: int, heads: int,
                                kv_heads: int, head_dim: int, ffn: int,
                                batch: int, itemsize: int, tp: int,
                                gated: bool = False,
                                vmem_budget: int = DEFAULT_VMEM_BUDGET):
    """Mirror of ``kernels.decode_block_tp.plan_decode_block_tp``."""
    rep = heads // kv_heads
    dh = head_dim
    h_l = heads // tp
    kh_l = kv_heads // tp
    f_l = ffn // tp
    b_l = batch // tp
    qkv_l = (h_l + 2 * kh_l) * dh
    up_l = f_l * (2 if gated else 1)
    bk, vmem_attn = _mirror_plan_slab_attention(
        max_seq, kh_l, rep, dh, itemsize, vmem_budget)
    if bk is None:
        return None, (f"vmem: tp attention residents {vmem_attn} bytes "
                      f"exceed budget {vmem_budget} even at block_k=8")
    entry_fixed = b_l * hidden * (itemsize + 4)
    entry_unit = 2 * (hidden + b_l + 1) * itemsize
    block_qkv = _mirror_fit_tile(qkv_l, entry_unit, entry_fixed,
                                 vmem_budget)
    if block_qkv is None:
        return None, (f"vmem: tp entry residents {entry_fixed} + weight "
                      f"tiles exceed budget {vmem_budget} at any tile of "
                      f"the per-device QKV width {qkv_l}")
    block_up = _mirror_fit_tile(up_l, entry_unit, entry_fixed,
                                vmem_budget)
    if block_up is None:
        return None, (f"vmem: tp entry residents {entry_fixed} + weight "
                      f"tiles exceed budget {vmem_budget} at any tile of "
                      f"the per-device MLP-up width {up_l}")
    exit_fixed = b_l * hidden * (4 + itemsize)
    exit_unit = 2 * (hidden + b_l) * itemsize
    block_o = _mirror_fit_tile(h_l * dh, exit_unit, exit_fixed,
                               vmem_budget)
    if block_o is None:
        return None, (f"vmem: tp exit residents {exit_fixed} + tiles "
                      f"exceed budget {vmem_budget} at any tile of the "
                      f"per-device out-proj rows {h_l * dh}")
    down_unit = exit_unit + 2 * b_l * itemsize * (1 if gated else 0)
    block_down = _mirror_fit_tile(f_l, down_unit, exit_fixed,
                                  vmem_budget)
    if block_down is None:
        return None, (f"vmem: tp exit residents {exit_fixed} + tiles "
                      f"exceed budget {vmem_budget} at any tile of the "
                      f"per-device MLP-down rows {f_l}")
    return {"block_k": bk, "block_qkv": block_qkv, "block_up": block_up,
            "block_o": block_o, "block_down": block_down,
            "vmem_attn": vmem_attn,
            "vmem_entry": entry_fixed
            + entry_unit * max(block_qkv, block_up),
            "vmem_exit": exit_fixed
            + max(exit_unit * block_o, down_unit * block_down)}, None


PLAN_MIRRORS = {
    "plan_decode_block": mirror_plan_decode_block,
    "plan_decode_block_tp": mirror_plan_decode_block_tp,
}

# the reference configuration the capacity manifest is evaluated at
# (the analyser's own constant: a GPT-2-small-width decode shape) with
# the engine's default block ladder (num_blocks = num_slots * max_seq /
# block_len)
REFERENCE_ENV: Dict[str, int] = {
    "vocab_size": 32768, "hidden": 768, "num_heads": 12, "kv_heads": 12,
    "head_dim": 64, "ffn": 3072, "num_layers": 12, "max_seq": 1024,
    "num_slots": 8, "block_len": 16, "num_blocks": 512, "itemsize": 2,
    "state_bytes_per_slot": 0, "v_slabs": 12,
}

# every tiling the static VMEM check proves: the flagship decode shape
# at both serving dtypes (+ the gated MLP variant), the CPU-smoke tiny
# shape, and the sharded plans at tp in {2, 4}
_FLAGSHIP = {"max_seq": 1024, "hidden": 768, "heads": 12, "kv_heads": 12,
             "head_dim": 64, "ffn": 3072, "batch": 8}
_TINY = {"max_seq": 128, "hidden": 64, "heads": 4, "kv_heads": 4,
         "head_dim": 16, "ffn": 256, "batch": 4}
REFERENCE_TILINGS: Tuple[Dict, ...] = (
    {"name": "flagship-bf16", "plan": "plan_decode_block",
     "kwargs": dict(_FLAGSHIP, itemsize=2)},
    {"name": "flagship-f32", "plan": "plan_decode_block",
     "kwargs": dict(_FLAGSHIP, itemsize=4)},
    {"name": "flagship-bf16-gated", "plan": "plan_decode_block",
     "kwargs": dict(_FLAGSHIP, itemsize=2, gated=True)},
    {"name": "tiny-f32", "plan": "plan_decode_block",
     "kwargs": dict(_TINY, itemsize=4)},
    {"name": "flagship-bf16-tp2", "plan": "plan_decode_block_tp",
     "kwargs": dict(_FLAGSHIP, itemsize=2, tp=2)},
    {"name": "flagship-bf16-tp4", "plan": "plan_decode_block_tp",
     "kwargs": dict(_FLAGSHIP, itemsize=2, tp=4)},
    {"name": "tiny-f32-tp2", "plan": "plan_decode_block_tp",
     "kwargs": dict(_TINY, itemsize=4, tp=2)},
)


def check_vmem_plan(plan_name: str, budget: int) -> List[Dict]:
    """Evaluate every reference tiling of ``plan_name`` through its
    mirror against ``budget``.  One row per tiling: ``ok`` means the
    plan produced a tiling AND every per-grid-step leg fits."""
    mirror = PLAN_MIRRORS.get(plan_name)
    rows: List[Dict] = []
    if mirror is None:
        return rows
    for t in REFERENCE_TILINGS:
        if t["plan"] != plan_name:
            continue
        plan, reason = mirror(vmem_budget=budget, **t["kwargs"])
        legs = {k: v for k, v in sorted((plan or {}).items())
                if k.startswith("vmem_")}
        rows.append({
            "tiling": t["name"], "plan": plan_name, "budget": budget,
            "working_set": legs,
            "ok": plan is not None and all(v <= budget
                                           for v in legs.values()),
            "reason": reason,
        })
    return rows


# --------------------------------------------------- the memory surface

# observable build counter: the checker's token gate is tested against
# it — an inert file must never pay for surface construction
BUILD_COUNT = 0

# persistent device allocations (``self.x = jnp.zeros(...)``) in the
# engine plane are only fixed-footprint when their owner is one of the
# init/rebuild constructors — anything else allocates after warmup
ALLOWED_ALLOC_OWNERS = frozenset({
    "__init__", "create", "reset", "_build_device_plane",
})


@dataclass
class PoolAttr:
    """One device slab attribute of a pool class."""
    name: str
    dims: Tuple[object, ...]        # int | capacity-field name | expr str
    count: object = 1               # per-layer listcomp multiplier
    itemsize: object = "itemsize"   # int | the symbolic element size
    line: int = 0
    bad_dims: Tuple[str, ...] = ()  # dims not flowing from capacity fields

    def formula(self) -> str:
        factors: List[str] = []
        if self.count != 1:
            factors.append(str(self.count))
        factors.extend(str(d) for d in self.dims)
        factors.append(str(self.itemsize))
        return " * ".join(factors)


@dataclass
class PoolSpec:
    qname: str
    module: str
    relpath: str
    line: int
    attrs: Dict[str, PoolAttr] = field(default_factory=dict)
    extra_capacity: Tuple[str, ...] = ()

    def formula(self) -> str:
        return " + ".join(self.attrs[a].formula()
                          for a in sorted(self.attrs))

    @property
    def capacity_ok(self) -> bool:
        return not any(a.bad_dims for a in self.attrs.values())


@dataclass
class VmemPlanDecl:
    plan: str
    module: str
    relpath: str
    line: int          # the __vmem_plans__ marker line
    budget: int
    budget_source: str  # "module" | "import" | "default"
    rows: List[Dict] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(r["ok"] for r in self.rows)


@dataclass
class AllocSite:
    module: str
    relpath: str
    line: int
    attr: str          # the self.<attr> target
    owner: str         # enclosing function name

    @property
    def allowed(self) -> bool:
        return self.owner in ALLOWED_ALLOC_OWNERS


@dataclass
class MemorySurface:
    pools: Dict[str, PoolSpec] = field(default_factory=dict)
    declared: Dict[str, Dict[str, str]] = field(default_factory=dict)
    vmem_plans: List[VmemPlanDecl] = field(default_factory=list)
    alloc_sites: List[AllocSite] = field(default_factory=list)

    def pools_for(self, relpath: str) -> List[PoolSpec]:
        return [p for p in self.pools.values() if p.relpath == relpath]

    def plans_for(self, relpath: str) -> List[VmemPlanDecl]:
        return [p for p in self.vmem_plans if p.relpath == relpath]


# ---- AST helpers ------------------------------------------------------

def _attr_leaf(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _module_dunder(tree: ast.Module, name: str) -> Optional[ast.AST]:
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    return stmt
    return None


def _dunder_tuple(tree: ast.Module, name: str) -> Tuple[Tuple[str, ...], int]:
    stmt = _module_dunder(tree, name)
    if stmt is None:
        return (), 0
    try:
        val = ast.literal_eval(stmt.value)
    except (ValueError, SyntaxError):
        return (), stmt.lineno
    if isinstance(val, (tuple, list)) and all(isinstance(v, str)
                                              for v in val):
        return tuple(val), stmt.lineno
    return (), stmt.lineno


def _dunder_dict(tree: ast.Module, name: str) -> Dict[str, str]:
    stmt = _module_dunder(tree, name)
    if stmt is None:
        return {}
    try:
        val = ast.literal_eval(stmt.value)
    except (ValueError, SyntaxError):
        return {}
    if isinstance(val, dict) and all(
            isinstance(k, str) and isinstance(v, str)
            for k, v in val.items()):
        return dict(val)
    return {}


def _module_int_const(tree: ast.Module, name: str) -> Optional[int]:
    stmt = _module_dunder(tree, name)
    if stmt is None:
        return None
    return _fold_int(stmt.value)


def _self_attr_assign(node: ast.AST):
    """``(attr, value, lineno)`` for a ``self.x = ...`` statement —
    plain or annotated (``self.ks: List[jax.Array] = [...]``)."""
    if isinstance(node, ast.Assign) and len(node.targets) == 1:
        tgt, val = node.targets[0], node.value
    elif isinstance(node, ast.AnnAssign) and node.value is not None:
        tgt, val = node.target, node.value
    else:
        return None
    if isinstance(tgt, ast.Attribute) and isinstance(tgt.value, ast.Name) \
            and tgt.value.id == "self":
        return tgt.attr, val, node.lineno
    return None


def _find_alloc_call(node: ast.AST, leaves: frozenset) -> Optional[ast.Call]:
    """First allocator call anywhere inside ``node`` (covers the direct
    form, the listcomp element and wrappers like ``replicated(...)``)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _attr_leaf(sub.func) in leaves:
            return sub
    return None


def _dtype_itemsize(call: ast.Call):
    """Element size of an allocator call: a concrete dtype leaf folds
    to bytes; a symbolic dtype (the pool's ``dtype`` parameter) stays
    the ``itemsize`` capacity symbol."""
    arg = None
    if len(call.args) >= 2:
        arg = call.args[1]
    else:
        for kw in call.keywords:
            if kw.arg == "dtype":
                arg = kw.value
    if arg is None:
        return 4                      # jnp default float32
    leaf = _attr_leaf(arg)
    size = itemsize_bytes(leaf)
    return size if size is not None else "itemsize"


def _dim_entries(shape_node: ast.AST, capacity: frozenset):
    """(dims, bad) for a shape tuple: each dim folds to an int, a
    capacity-field name, or a textual expression; names (including
    names inside dim expressions) outside the capacity set are bad."""
    if not isinstance(shape_node, ast.Tuple):
        return None, ()
    dims: List[object] = []
    bad: List[str] = []
    for el in shape_node.elts:
        folded = _fold_int(el)
        if folded is not None:
            dims.append(folded)
            continue
        names = sorted({_attr_leaf(n) or n.id
                        for n in ast.walk(el)
                        if isinstance(n, (ast.Name, ast.Attribute))
                        and not isinstance(n, ast.Attribute)
                        } | {n.attr for n in ast.walk(el)
                             if isinstance(n, ast.Attribute)})
        names = [n for n in names if n is not None]
        bad.extend(n for n in names if n not in capacity)
        if isinstance(el, ast.Name):
            dims.append(el.id)
        elif isinstance(el, ast.Attribute):
            dims.append(el.attr)
        else:
            dims.append(ast.unparse(el))
    return tuple(dims), tuple(bad)


def _walk_pool_class(cls_node: ast.ClassDef, module: str, relpath: str,
                     capacity: frozenset,
                     leaves: frozenset) -> Optional[PoolSpec]:
    init = None
    for stmt in cls_node.body:
        if isinstance(stmt, ast.FunctionDef) and stmt.name == "__init__":
            init = stmt
            break
    if init is None:
        return None
    spec = PoolSpec(qname=f"{module}.{cls_node.name}", module=module,
                    relpath=relpath, line=cls_node.lineno)
    # the constructor's shape locals: shape = (num_slots, max_seq, ...)
    shape_locals: Dict[str, ast.Tuple] = {}
    for node in ast.walk(init):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Tuple):
            shape_locals[node.targets[0].id] = node.value
    for node in ast.walk(init):
        hit = _self_attr_assign(node)
        if hit is None:
            continue
        attr_name, value, lineno = hit
        if attr_name in spec.attrs:      # mesh/else branch: first wins
            continue
        count: object = 1
        if isinstance(value, ast.ListComp):
            gen = value.generators[0]
            if isinstance(gen.iter, ast.Call) \
                    and _attr_leaf(gen.iter.func) == "range" \
                    and len(gen.iter.args) == 1:
                folded = _fold_int(gen.iter.args[0])
                if folded is not None:
                    count = folded
                elif isinstance(gen.iter.args[0], ast.Name):
                    count = gen.iter.args[0].id
        call = _find_alloc_call(value, leaves)
        if call is None or not call.args:
            continue
        shape_arg = call.args[0]
        if isinstance(shape_arg, ast.Name):
            shape_arg = shape_locals.get(shape_arg.id)
            if shape_arg is None:
                continue
        dims, bad = _dim_entries(shape_arg, capacity)
        if dims is None:
            continue
        spec.attrs[attr_name] = PoolAttr(
            name=attr_name, dims=dims, count=count,
            itemsize=_dtype_itemsize(call), line=lineno,
            bad_dims=bad)
    return spec if spec.attrs else None


def build_memory_surface(project) -> MemorySurface:
    """One pass over the project index: pool slab derivation, declared
    byte legs, VMEM plan declarations (budget folded from the declaring
    module, resolved through imports), persistent alloc sites."""
    global BUILD_COUNT
    BUILD_COUNT += 1
    surface = MemorySurface()
    leaves = _allocator_leaves()
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        tree = mod.tree
        extra, _ = _dunder_tuple(tree, CAPACITY_DUNDER)
        capacity = registered_capacity_fields() | frozenset(extra)
        declared = _dunder_dict(tree, MEMORY_BYTES_DUNDER)
        if declared:
            surface.declared[mod.name] = declared
        # pool classes: constructor slab derivation
        for stmt in tree.body:
            if isinstance(stmt, ast.ClassDef) and "Pool" in stmt.name:
                spec = _walk_pool_class(stmt, mod.name, mod.relpath,
                                        capacity, leaves)
                if spec is not None:
                    spec.extra_capacity = extra
                    surface.pools[spec.qname] = spec
        # VMEM plan declarations
        plans, line = _dunder_tuple(tree, VMEM_PLANS_DUNDER)
        if plans:
            budget = _module_int_const(tree, "VMEM_BUDGET")
            source = "module"
            if budget is None:
                target = mod.imports.get("VMEM_BUDGET")
                if target and "." in target:
                    src_mod = project.modules.get(
                        target.rsplit(".", 1)[0])
                    if src_mod is not None:
                        budget = _module_int_const(
                            src_mod.tree, target.rsplit(".", 1)[1])
                        source = "import"
            if budget is None:
                budget, source = DEFAULT_VMEM_BUDGET, "default"
            for plan in plans:
                surface.vmem_plans.append(VmemPlanDecl(
                    plan=plan, module=mod.name, relpath=mod.relpath,
                    line=line, budget=budget, budget_source=source,
                    rows=check_vmem_plan(plan, budget)))
        # persistent device allocations (self.<attr> = ...alloc...)
        for cls in tree.body:
            if not isinstance(cls, ast.ClassDef):
                continue
            for fn in cls.body:
                if not isinstance(fn, ast.FunctionDef):
                    continue
                for node in ast.walk(fn):
                    hit = _self_attr_assign(node)
                    if hit is None:
                        continue
                    attr_name, value, lineno = hit
                    if _find_alloc_call(value, leaves) is not None:
                        surface.alloc_sites.append(AllocSite(
                            module=mod.name, relpath=mod.relpath,
                            line=lineno, attr=attr_name,
                            owner=fn.name))
    surface.vmem_plans.sort(key=lambda p: (p.relpath, p.plan))
    surface.alloc_sites.sort(key=lambda s: (s.relpath, s.line))
    return surface


def memory_surface_for(project) -> MemorySurface:
    """Per-project surface cache (the checker and the manifest share
    one build per analysis run — same contract as graftprog's
    ``surface_for``)."""
    surf = getattr(project, "_graftmem_surface", None)
    if surf is None:
        surf = build_memory_surface(project)
        setattr(project, "_graftmem_surface", surf)
    return surf


# ----------------------------------------------------------- manifest

# mirrors models/gpt.GPTConfig.num_params at the reference posture
# (use_bias=True, tie_embeddings=True) — the weights leg of every
# program footprint
WEIGHT_PARAM_FORMULA = ("vocab_size * hidden + max_seq * hidden"
                        " + num_layers * (4 * hidden * hidden"
                        " + 2 * hidden * ffn + 9 * hidden + 2 * ffn)"
                        " + 2 * hidden")

# per-counter activation estimates (f32 logits; four live residual-wide
# tensors is the deepest simultaneous window of the decode/prefill step)
ACTIVATION_FORMULAS = {
    "decode": "4 * num_slots * hidden * itemsize"
              " + num_slots * vocab_size * 4",
    "verify": "4 * num_slots * hidden * itemsize"
              " + num_slots * vocab_size * 4",
    "prefill": "4 * max_seq * hidden * itemsize + vocab_size * 4",
    "gather": "0",
    "scatter": "0",
}
_DEFAULT_ACTIVATION = "4 * max_seq * hidden * itemsize + vocab_size * 4"

# which derived pools each counter's program touches
COUNTER_POOLS = {
    "decode": ("KVPool",),
    "verify": ("KVPool",),
    "prefill": ("KVPool",),
    "gather": ("KVPool", "BlockPool"),
    "scatter": ("KVPool", "BlockPool"),
}


def _pool_by_leaf(surface: MemorySurface, leaf: str) -> Optional[PoolSpec]:
    for qname in sorted(surface.pools):
        if qname.rsplit(".", 1)[-1] == leaf:
            return surface.pools[qname]
    return None


def _safe_eval(formula: str, env: Dict[str, int]) -> Optional[int]:
    try:
        return eval_formula(formula, env)
    except FormulaError:
        return None


def _declared_legs(surface: MemorySurface):
    """(row_state formulas, staging formula) folded over every module's
    MEMORY_BYTES_DUNDER declaration."""
    row_state: Dict[str, str] = {}
    staging: Optional[str] = None
    for mod in sorted(surface.declared):
        for key, formula in sorted(surface.declared[mod].items()):
            if key.startswith("row_state."):
                row_state[key.split(".", 1)[1]] = formula
            elif key == "staging":
                staging = formula
    return row_state, staging


def build_memory_manifest(project) -> Dict:
    """The deterministic HBM capacity manifest — ROADMAP direction 3's
    build input.  Pure data: formulas plus their values at the
    reference environment; byte-identical across runs over identical
    sources."""
    from .compile_surface import surface_for
    surface = memory_surface_for(project)
    prog = surface_for(project)
    env = dict(REFERENCE_ENV)
    row_state, staging = _declared_legs(surface)

    pools_out: Dict[str, Dict] = {}
    for qname in sorted(surface.pools):
        spec = surface.pools[qname]
        pools_out[qname] = {
            "formula": spec.formula(),
            "bytes_at_reference": _safe_eval(spec.formula(), env),
            "capacity_ok": spec.capacity_ok,
            "attrs": {a: {"dims": [str(d) for d in spec.attrs[a].dims],
                          "count": str(spec.attrs[a].count),
                          "itemsize": str(spec.attrs[a].itemsize),
                          "line": spec.attrs[a].line}
                      for a in sorted(spec.attrs)},
            "evidence": f"{spec.relpath}:{spec.line}",
        }

    # ---- the KV tier: bytes per block, ladder per chip
    kv_tier: Dict = {}
    block_pool = _pool_by_leaf(surface, "BlockPool")
    kv_pool = _pool_by_leaf(surface, "KVPool")
    weights_bytes = eval_formula(WEIGHT_PARAM_FORMULA, env) \
        * env["itemsize"]
    if block_pool is not None:
        per_block_factors: List[str] = []
        for a in sorted(block_pool.attrs):
            attr = block_pool.attrs[a]
            dims = [str(d) for d in attr.dims if str(d) != "num_blocks"]
            fac = [str(attr.count)] if attr.count != 1 else []
            per_block_factors.append(
                " * ".join(fac + dims + [str(attr.itemsize)]))
        per_block_formula = " + ".join(per_block_factors)
        per_block = {
            "bfloat16": _safe_eval(per_block_formula,
                                   dict(env, itemsize=2)),
            "int8": _safe_eval(per_block_formula, dict(env, itemsize=1)),
        }
        fixed = weights_bytes
        for p in (kv_pool,):
            if p is not None:
                fixed += _safe_eval(p.formula(), env) or 0
        for formula in sorted(row_state.values()):
            fixed += _safe_eval(formula, env) or 0
        if staging:
            fixed += _safe_eval(staging, env) or 0
        ladder = {}
        for chip in sorted(CHIP_HBM_BYTES):
            avail = CHIP_HBM_BYTES[chip] - fixed
            ladder[chip] = {
                dt: max(0, avail // per_block[dt])
                if per_block[dt] else 0
                for dt in sorted(per_block)}
        kv_tier = {
            "bytes_per_block_formula": per_block_formula,
            "bytes_per_block": per_block,
            "kv_bytes_per_token": {
                dt: (per_block[dt] or 0) // env["block_len"]
                for dt in sorted(per_block)},
            "block_len": env["block_len"],
            "fixed_plane_bytes": fixed,
            "max_resident_blocks": ladder,
        }

    # ---- VMEM: every declared plan over the reference tilings
    vmem_out = {
        "default_budget": DEFAULT_VMEM_BUDGET,
        "plans": {
            p.plan: {"module": p.module, "budget": p.budget,
                     "budget_source": p.budget_source,
                     "declared_at": f"{p.relpath}:{p.line}",
                     "ok": p.ok, "tilings": p.rows}
            for p in surface.vmem_plans},
        "all_ok": all(p.ok for p in surface.vmem_plans),
    }

    # ---- per-program peak residents over the graftprog planes
    programs: List[Dict] = []
    plane_units = sorted(
        (u for u in prog.units if u.counter is not None and u.roots),
        key=lambda u: u.uid)
    for u in plane_units:
        legs: Dict[str, int] = {"weights": weights_bytes}
        pool_bytes = 0
        for leaf in COUNTER_POOLS.get(u.counter, ()):
            p = _pool_by_leaf(surface, leaf)
            if p is not None:
                pool_bytes += _safe_eval(p.formula(), env) or 0
        donated = bool(u.donate)
        legs["pools"] = pool_bytes if donated else 2 * pool_bytes
        legs["row_state"] = sum(_safe_eval(f, env) or 0
                                for f in row_state.values())
        legs["staging"] = (_safe_eval(staging, env) or 0) if staging \
            else 0
        act = ACTIVATION_FORMULAS.get(u.counter, _DEFAULT_ACTIVATION)
        legs["activations"] = _safe_eval(act, env) or 0
        programs.append({
            "uid": u.uid, "counter": u.counter, "kind": u.kind,
            "donated": donated,
            "donation_note": "slabs updated in place — counted once"
            if donated else "undonated — slabs counted input + output",
            "legs": legs,
            "activation_formula": act,
            "peak_bytes": sum(legs.values()),
        })

    # ---- the EngineCore plane: the fixed-footprint proof
    planes: Dict[str, Dict] = {}
    engine_mod = None
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        if "EngineCore" in getattr(mod, "classes", {}):
            engine_mod = mod
            break
    if engine_mod is not None:
        plane_modules = {engine_mod.name}
        for qname in surface.pools:
            plane_modules.add(surface.pools[qname].module)
        sites = [s for s in surface.alloc_sites
                 if s.module in plane_modules]
        rogue = [s for s in sites if not s.allowed]
        plane_pool_bytes = sum(
            _safe_eval(surface.pools[q].formula(), env) or 0
            for q in sorted(surface.pools)
            if surface.pools[q].module in plane_modules)
        planes[f"{engine_mod.name}.EngineCore"] = {
            "fixed_footprint": not rogue,
            "alloc_sites": [
                {"attr": s.attr, "owner": s.owner, "allowed": s.allowed,
                 "at": f"{s.relpath}:{s.line}"} for s in sites],
            "pool_bytes_at_reference": plane_pool_bytes,
            "row_state": {k: {"formula": f,
                              "bytes_at_reference": _safe_eval(f, env)}
                          for k, f in sorted(row_state.items())},
            "staging": {"formula": staging,
                        "bytes_at_reference": _safe_eval(staging, env)
                        if staging else None},
        }

    return {
        "graftmem_version": GRAFTMEM_VERSION,
        "fingerprint": memory_fingerprint(),
        "reference_env": env,
        "byte_semantics": {
            "itemsize_bytes": {d: itemsize_bytes(d) for d in sorted((
                "bfloat16", "bool", "float16", "float32", "float64",
                "int8", "int32", "int64", "uint32"))},
            "signatures": registered_byte_signatures(),
            "weight_params_formula": WEIGHT_PARAM_FORMULA,
            "weights_bytes_at_reference": weights_bytes,
        },
        "capacity_fields": sorted(registered_capacity_fields()),
        "chips_hbm_bytes": dict(sorted(CHIP_HBM_BYTES.items())),
        "pools": pools_out,
        "kv_tier": kv_tier,
        "vmem": vmem_out,
        "programs": programs,
        "planes": planes,
    }


def build_memory_manifest_for_paths(paths: Sequence[str],
                                    root: Optional[str] = None,
                                    cache_path: Optional[str] = None
                                    ) -> Dict:
    """Parse ``paths`` (through the shared on-disk parse cache when
    given), build the project index, and return the capacity manifest —
    the CLI's ``--memory`` entry point and the runtime consistency
    test's library hook."""
    import os
    from pathlib import Path
    from .walker import _ParseCache, _parse_files
    from .project import build_project
    root_str = str(Path(root).resolve()) if root else os.getcwd()
    cache = _ParseCache(cache_path)
    parsed = _parse_files(paths, root_str, cache)
    cache.save()
    project = build_project((pf.relpath, pf.tree, pf.sup)
                            for pf in parsed.values()
                            if pf.tree is not None)
    return build_memory_manifest(project)
