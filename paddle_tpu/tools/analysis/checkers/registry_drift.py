"""registry-drift: the op registry and the public API must agree.

Two directions, both derived purely from source (no imports, so this
runs anywhere the tree checks out):

  1. every ``T.xxx`` / ``F.yyy`` / ``T.linalg.zzz`` reference inside
     ``ops/defs.py`` must resolve to a public callable actually defined
     (or aliased) in ``paddle_tpu/tensor/`` / ``paddle_tpu/nn/functional/``
     — a registry entry pointing at nothing is a broken OpTest row;
  2. every public top-level function in those surfaces must either be
     referenced by the registry or carry an entry in ``ALLOWLIST`` below
     (the audit trail for WHY an op is outside the numeric harness —
     same discipline as ``OpDef.grad_exempt``).

This one pass replaces the per-script resolve logic that previously
lived only in ``scripts/gen_op_coverage.py``'s doc generator — drift now
fails the lint gate, not just a docs diff.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from ..findings import Finding, ERROR
from .base import Checker, dotted_name

# public surface entries exempt from registration, with the reason.
# Grouped by exemption class; every entry is name -> why it is not in the
# OpTest registry.  New public functions must either register or land here.
_STOCHASTIC = "stochastic output — no deterministic numpy oracle for OpTest"
_INPLACE = "in-place alias of a registered out-of-place op"
_CONSTRUCTOR = "constructor/initializer — no differentiable inputs; covered by creation-path tests"
_PREDICATE = "host predicate/introspection helper, not an array op"
_COMPOSITE = "composite wrapper over registered primitives; covered by module-level tests"
_NN_LAYER_PATH = "exercised through its nn.Layer wrapper in layer tests"
_SPECIALIZED = "specialized op with dedicated tests outside the registry harness"
_SERVING = ("serving control-plane API (request lifecycle / scheduling / "
            "metrics), not an array op; covered by tests/test_serving.py")
_OBS = ("observability control-plane (metrics registry / spans / event "
        "log), pure host code with no array inputs; covered by "
        "tests/test_observability.py")

ALLOWLIST: Dict[str, str] = {
    # ---- stochastic samplers (tensor/random.py + dropout family)
    **{n: _STOCHASTIC for n in (
        "bernoulli", "bernoulli_", "binomial", "cauchy_", "exponential_",
        "geometric_", "log_normal", "log_normal_", "multinomial", "normal",
        "normal_", "poisson", "rand", "randint", "randint_like", "randn",
        "randperm", "standard_gamma", "standard_normal", "uniform",
        "uniform_", "dropout", "dropout2d", "dropout3d", "alpha_dropout",
        "feature_alpha_dropout", "rrelu", "gumbel_softmax",
        "fractional_max_pool2d", "fractional_max_pool3d",
        "class_center_sample",
    )},
    # ---- in-place variants
    **{n: _INPLACE for n in (
        "add_", "clip_", "fill_", "fill_diagonal_", "fill_diagonal_tensor_",
        "flatten_", "scale_", "squeeze_", "unsqueeze_", "reshape_",
        "zero_", "elu_", "leaky_relu_", "relu_", "sigmoid_", "tanh_",
        "softmax_", "multiply_", "erfc_", "bitwise_invert_", "where_",
    )},
    # ---- constructors / conversion
    **{n: _CONSTRUCTOR for n in (
        "arange", "as_complex", "as_real", "as_strided", "as_tensor",
        "assign", "cast", "clone", "complex", "create_parameter",
        "diag_embed", "empty", "empty_like", "eye", "full", "full_like",
        "linspace", "logspace", "meshgrid", "ones", "ones_like",
        "to_tensor", "tril_indices", "triu_indices", "zeros", "zeros_like",
        "one_hot", "sequence_mask",
    )},
    # ---- host predicates / introspection / printing
    **{n: _PREDICATE for n in (
        "get_printoptions", "set_printoptions", "is_complex", "is_empty",
        "is_floating_point", "is_integer", "is_tensor", "isreal",
        "index_of", "rank", "shard_index", "broadcast_shape",
        "numel", "shape", "builtins_slice",
    )},
    # ---- composites over registered primitives
    **{n: _COMPOSITE for n in (
        "atleast_1d", "atleast_2d", "atleast_3d", "broadcast_tensors",
        "cartesian_prod", "chunk", "combinations", "cond",
        "diagonal_scatter", "fill_diagonal_tensor", "histogramdd",
        "increment", "index_put", "masked_scatter", "matrix_exp",
        "put_along_axis", "select_scatter", "slice_scatter", "vander",
        "view", "view_as", "unflatten", "moveaxis", "rot90",
        "row_stack", "subtract", "tensor_split", "tolist", "trapezoid",
        "cumulative_trapezoid", "unique_consecutive", "block_diag",
        "scatter_nd", "slice", "strided_slice", "multiplex", "renorm",
        "polar", "bitwise_invert",
        "cosine_similarity", "cosine_embedding_loss", "label_smooth",
        "normalize", "upsample", "zeropad2d", "channel_shuffle",
        "pixel_shuffle", "pixel_unshuffle", "interpolate",
        "affine_grid", "grid_sample", "temporal_shift",
        "bilinear", "maxout", "sparse_attention", "gather_tree",
    )},
    # ---- linalg solvers / decompositions (iterative or LAPACK-backed;
    #      dedicated tests in test_tensor_longtail / test_functional)
    **{n: _SPECIALIZED for n in (
        "cholesky_inverse", "eig", "eigh", "eigvals", "eigvalsh",
        "lu_solve", "lu_unpack", "matrix_rank", "multi_dot", "ormqr",
        "pca_lowrank", "svd", "svd_lowrank", "triangular_solve",
    )},
    # ---- nn.functional surfaces exercised through nn.Layer wrappers
    **{n: _NN_LAYER_PATH for n in (
        "adaptive_avg_pool1d", "adaptive_avg_pool3d",
        "adaptive_max_pool1d", "adaptive_max_pool2d",
        "adaptive_max_pool3d", "avg_pool1d", "avg_pool3d", "max_pool1d",
        "max_pool3d", "max_unpool1d", "max_unpool2d", "max_unpool3d",
        "lp_pool1d", "lp_pool2d", "conv1d_transpose", "conv2d_transpose",
        "conv3d", "conv3d_transpose", "fold", "unfold", "group_norm",
        "instance_norm", "local_response_norm", "celu", "hardtanh",
        "log_sigmoid", "prelu", "selu", "softshrink", "swish",
        "thresholded_relu", "tanh", "gelu",
    )},
    # ---- loss surfaces with dedicated test files (shape/reduction
    #      semantics beyond the element-wise OpTest harness)
    **{n: _SPECIALIZED for n in (
        "adaptive_log_softmax_with_loss", "binary_cross_entropy",
        "binary_cross_entropy_with_logits", "chunked_softmax_cross_entropy",
        "ctc_loss", "dice_loss", "gaussian_nll_loss",
        "hinge_embedding_loss", "hsigmoid_loss", "kl_div", "l1_loss",
        "log_loss", "margin_cross_entropy", "margin_ranking_loss",
        "mse_loss", "multi_label_soft_margin_loss", "multi_margin_loss",
        "nll_loss", "npair_loss", "poisson_nll_loss", "rnnt_loss",
        "sigmoid_focal_loss", "smooth_l1_loss", "soft_margin_loss",
        "softmax_with_cross_entropy", "square_error_cost",
        "triplet_margin_loss", "triplet_margin_with_distance_loss",
    )},
    # ---- attention / fused paths (tested in test_pallas_kernels,
    #      test_incubate_fused, test_functional attention suites)
    **{n: _SPECIALIZED for n in (
        "flash_attention", "flash_attn_unpadded",
        "scaled_dot_product_attention", "sdpa_reference", "swiglu",
    )},
    # ---- paddle_tpu.serving public surface (the SRV registry surface:
    #      engine/scheduler/pool classes and their helpers are request
    #      lifecycle, not numeric ops — the OpTest harness has no oracle
    #      for them; tests/test_serving.py + test_prefix_cache.py are
    #      their contract)
    **{n: _SERVING for n in (
        "ServingEngine", "EngineCore", "Request", "RequestOutput",
        "SamplingParams", "Scheduler", "KVPool", "ServingMetrics",
        "StepSpans",
        "bucket_length", "sample_rows", "BlockPool", "PrefixCache",
        "MatchResult",
        # fault-tolerance surface (ISSUE 8): watchdog/ladder/injection
        # control plane + the in-program health probe; contract =
        # tests/test_zz_chaos_serving.py
        "FaultToleranceConfig", "EngineHealth", "DegradationLadder",
        "FaultInjector", "FaultError", "RequestRejected",
        "EngineStalledError", "finite_or_sentinel",
        # tensor-parallel serving plumbing (ISSUE 9): mesh/layout
        # builders and the shard_map decode-program factory — sharding
        # control plane, not array ops; contract =
        # tests/test_zz_tp_serving.py
        "build_serving_mesh", "serving_param_specs",
        "shard_model_params", "sharded_zeros", "replicated",
        "tp_decode_supported", "build_tp_decode_program",
        # fleet tier (ISSUE 10): the replica router and the fleet
        # accounting verdict — request routing / failover control
        # plane, not array ops; contract =
        # tests/test_zz_fleet_serving.py
        "Router", "ReplicaHandle", "fleet_accounting",
        "replica_accounting",
        # disaggregated fleet (ISSUE 13): the KV handoff state machine
        # and the drain-based autoscaler — cross-replica transfer /
        # capacity control plane, not array ops; contract =
        # tests/test_zz_disagg_serving.py
        "Handoff", "HandoffManager", "Autoscaler",
        # crash consistency (ISSUE 14): the durable request journal
        # (append-only CRC-framed WAL) — pure host-side persistence
        # control plane, no array ops; contract =
        # tests/test_zz_crash_serving.py
        "Journal", "JournalError",
        # zero cold start (ISSUE 17): the manifest-driven AOT program
        # store — host-side artifact persistence + keying, no array
        # ops; contract = tests/test_zz_aot_serving.py
        "AOTStore", "AOTStoreWriter", "AOTStoreError",
        "build_engine_store", "engine_aot_context", "aot_fingerprint",
        # speculative decoding (ISSUE 18): the host-side n-gram draft
        # table and the shard_map verify-program factory — draft
        # control plane + sharding plumbing, not array ops; contract =
        # tests/test_zz_spec_serving.py
        "NGramDraftTable", "build_tp_verify_program",
        # KV planes (ISSUE 28): how many planes and slabs a model's
        # cache has, and why a looped cache cannot be kv-head sharded —
        # pool sizing control plane, not array ops; contract =
        # tests/test_ouro.py
        "cache_geometry", "serving_refusal",
        # recurrent state (ISSUE 32): what a model declares a slot holds
        # beside its KV rows, and its bytes — pool sizing control plane,
        # not array ops; contract = tests/test_jamba.py
        "recurrent_state_spec", "state_bytes", "zero_state",
        # what a cached position holds (ISSUE 34): K and V rows, or the
        # one latent row of a model that declares one row kind — pool
        # sizing control plane; contract = tests/test_deepseek_v3.py
        "cache_row",
    )},
    # ---- paddle_tpu.obs public surface (the OBS registry surface:
    #      counters/gauges/histograms and the span tracer are telemetry
    #      plumbing with no numeric oracle; tests/test_observability.py
    #      is their contract)
    **{n: _OBS for n in (
        "Counter", "Gauge", "Histogram", "MetricsRegistry", "Span",
        "Tracer",
        # obs/parts.py: op_name and HLO-text parsing, pure string work;
        # contract = tests/test_program_parts.py
        "parts_on_path", "part_of", "operation_key", "program_name",
        "program_parts",
    )},
}


class RegistryDriftChecker(Checker):
    name = "registry-drift"
    severity = ERROR

    def __init__(self, defs_path: str = "paddle_tpu/ops/defs.py",
                 surfaces: Optional[Dict[str, str]] = None,
                 allowlist: Optional[Dict[str, str]] = None):
        """``surfaces`` maps the defs-module alias (``T``/``F``) to the
        directory (relative to scan root) holding that public surface."""
        self.defs_path = defs_path
        self.surfaces = surfaces or {
            "T": "paddle_tpu/tensor",
            "F": "paddle_tpu/nn/functional",
            "SRV": "paddle_tpu/serving",
            "OBS": "paddle_tpu/obs",
        }
        self.allowlist = ALLOWLIST if allowlist is None else allowlist

    def check(self, ctx) -> List[Finding]:
        if ctx.relpath != self.defs_path:
            return []
        findings: List[Finding] = []
        refs = self._collect_refs(ctx.tree)
        root = Path(ctx.root)
        surfaces = {alias: _scan_surface(root / reldir, root)
                    for alias, reldir in self.surfaces.items()}

        # 1. every registry reference resolves
        for alias, dotted, node in refs:
            names, submods = surfaces.get(alias, ({}, {}))
            parts = dotted.split(".")
            if len(parts) == 1:
                ok = parts[0] in names
            elif len(parts) == 2 and parts[0] in submods:
                ok = parts[1] in submods[parts[0]]
            else:
                ok = False
            if not ok:
                findings.append(Finding(
                    self.name, ctx.relpath, node.lineno, node.col_offset,
                    f"registry references {alias}.{dotted} but no public "
                    f"def/alias with that name exists under "
                    f"{self.surfaces[alias]}/", self.severity))

        # 2. every public surface function is registered or allow-listed
        referenced = {d.split(".")[-1] for _, d, _ in refs}
        for alias, reldir in self.surfaces.items():
            names, _ = surfaces[alias]
            for name, (relfile, lineno) in sorted(names.items()):
                if name in referenced or name in self.allowlist:
                    continue
                findings.append(Finding(
                    self.name, relfile, lineno, 0,
                    f"public {alias}-surface function {name!r} is neither "
                    f"in the op registry nor allow-listed in "
                    f"registry_drift.ALLOWLIST (add a registration or an "
                    f"allowlist entry with a reason)", self.severity))
        return findings

    def _collect_refs(self, tree) -> List[Tuple[str, str, ast.AST]]:
        """(alias, dotted-remainder, node) for every T./F. attribute
        reference in defs.py, e.g. ('T', 'abs', ...), ('T',
        'linalg.vecdot', ...)."""
        aliases = set(self.surfaces)
        out: List[Tuple[str, str, ast.AST]] = []
        seen_ids = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Attribute):
                continue
            if id(node) in seen_ids:
                continue
            full = dotted_name(node)
            if full is None:
                continue
            root, _, rest = full.partition(".")
            if root in aliases and rest:
                out.append((root, rest, node))
                # don't double-report the inner Attribute of T.linalg.x
                inner = node.value
                while isinstance(inner, ast.Attribute):
                    seen_ids.add(id(inner))
                    inner = inner.value
        return out


def _scan_surface(dirpath: Path, root: Path):
    """Return ({public name: (relfile, lineno)}, {submodule: {names}}).

    Public = top-level ``def name`` or top-level ``name = <expr>`` alias,
    not underscore-prefixed, across every module in the directory.
    """
    names: Dict[str, Tuple[str, int]] = {}
    submods: Dict[str, Set[str]] = {}
    for p in sorted(dirpath.glob("*.py")):
        mod_names: Set[str] = set()
        try:
            tree = ast.parse(p.read_text())
        except SyntaxError:
            continue
        for n in tree.body:
            public: List[Tuple[str, int]] = []
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                public.append((n.name, n.lineno))
            elif isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        public.append((t.id, n.lineno))
            for name, lineno in public:
                if name.startswith("_") or name == name.upper():
                    continue  # private or module constant
                mod_names.add(name)
                if p.name != "__init__.py":
                    try:
                        rel = p.relative_to(root).as_posix()
                    except ValueError:
                        rel = p.as_posix()
                    names.setdefault(name, (rel, lineno))
        if p.name != "__init__.py":
            submods[p.stem] = mod_names
    return names, submods
