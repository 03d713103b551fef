"""Zero-cold-start AOT program store (docs/serving.md "Zero cold start").

The compile-surface manifest (PR 16, tools/analysis/compile_surface.py)
statically PROVES the serving engine's program set — ``{chunk} +
O(log2) prefill buckets + ONE decode + 1 gather + 1 scatter`` per
device plane.  This module turns that proof into a build input: the
builder AOT-lowers every manifest program on the ``EngineCore`` plane
through ``jit/_export_compat`` (jax.export) and persists the serialized
artifacts into an on-disk store; ``EngineCore(aot_store=...)`` then
LOADS instead of traces on startup, so an autoscaler spawn, a
resurrection or a quarantine rebuild is routable without paying a
single trace.

Store layout (one directory)::

    <store>/
      index.json          # atomic publish point: fingerprint + entries
      objects/<sha>.aot   # CRC-framed serialized jax.export artifacts

Framing and publish discipline mirror the request journal
(serving/journal.py): each object is one ``<u32 len><u32 crc32>``
frame, and the index lands via tmp-write + fsync + ``os.replace`` — a
crash mid-build leaves unreferenced objects (``aot_build.py gc``
collects them), never a half-published store.

Keying: the store carries ONE fingerprint — a sha256 over the
canonicalized (model config, serving config, tensor-parallel degree,
jax/jaxlib versions) tuple — and per-program entries named by their
manifest counter plus key-space leg (``prefill:w<width>`` per committed
bucket width, ``decode:<resolved path>``, ``gather``, ``scatter``).  An
engine whose fingerprint differs, or whose resolved leg is absent,
falls back loudly-but-gracefully to tracing (an ``aot_miss`` /
``aot_fallback`` degradation event, never a crash).  The writer refuses
to publish a store missing any manifest program id or holding a
program the manifest classifies unbounded — the completeness check the
manifest gives us for free.

Lifecycle (registered graftlint ResourcePairs): readers pair
``AOTStore.open`` with ``close``; builders pair ``AOTStore.create``
with ``publish`` (success) or ``discard`` (abort) on every path.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import struct
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..jit import _export_compat as _jx

__all__ = ["AOTStore", "AOTStoreWriter", "AOTStoreError",
           "build_engine_store", "engine_aot_context", "aot_fingerprint"]

# graftprog: the store's deserialize + jit re-wrap and the builder's
# export path are compile-surface units — the builder function and the
# reader class are their entry points (the engine reaches them only
# through a stored handle, which the static walk cannot follow)
__compile_surface_roots__ = ("build_engine_store", "AOTStore")

# version 2: the decode signature gained the constrained-decoding vocab
# mask operand and the plane gained the ONE verify program (ISSUE 18) —
# a version-1 store's decode artifact would be called with an operand it
# was never exported for, so open() refuses old stores outright instead
# of letting the mismatch surface as a shape error mid-serve
STORE_VERSION = 2
INDEX_NAME = "index.json"
OBJECTS_DIR = "objects"
ENGINE_PLANE = "paddle_tpu.serving.engine.EngineCore"

# journal-style CRC framing: (payload_len, crc32(payload)) prefix.  The
# length guard rejects garbage headers before a huge allocation.
_HEADER = struct.Struct("<II")
_MAX_PAYLOAD = 1 << 30


class AOTStoreError(RuntimeError):
    """A store-contract violation: unpublished/corrupt store, missing
    manifest coverage at publish, or builder/runtime bucket drift."""


# --------------------------------------------------------------- keying
def _canon(obj: Any) -> Any:
    """Canonical JSON-safe form: dicts sort, tuples become lists, and
    anything non-primitive (dtypes, enums) stringifies — the fingerprint
    must not depend on dict order or repr jitter."""
    if isinstance(obj, dict):
        return {str(k): _canon(obj[k]) for k in sorted(obj, key=str)}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    return str(obj)


def aot_fingerprint(context: Dict[str, Any]) -> str:
    """Deterministic store key: sha256 over the canonicalized context."""
    blob = json.dumps(_canon(context), sort_keys=True,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


def engine_aot_context(core) -> Dict[str, Any]:
    """The fingerprint preimage for one engine: everything that shapes
    a compiled program — model config, the RESOLVED serving geometry
    (pool max_seq, block_len, num_blocks — not the constructor args),
    tensor-parallel degree and the jax/jaxlib versions the artifacts
    were lowered under.  The decode path is deliberately NOT here: it
    keys the per-program leg (``decode:<path>``), so a fused and an
    unfused engine share one store fingerprint."""
    cfg = core.model.cfg
    model_ctx = dataclasses.asdict(cfg) if dataclasses.is_dataclass(cfg) \
        else dict(vars(cfg))
    try:
        import jaxlib.version as _jlv
        jaxlib_version = _jlv.__version__
    except Exception:
        jaxlib_version = "unknown"
    bp = core.block_pool
    return {
        "store_version": STORE_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "model_class": type(core.model).__name__,
        "model": model_ctx,
        "num_slots": core.num_slots,
        "max_seq": core.pool.max_seq,
        # the RESOLVED plane count (kv_pool.cache_geometry): for a
        # looped model a property of its config, not a field of it, and
        # it shapes every program's slab operands
        "kv_planes": core.pool.planes,
        "min_bucket": core.scheduler.min_bucket,
        "prefill_chunk": core.prefill_chunk,
        "block_len": bp.block_len if bp is not None else None,
        "num_blocks": bp.num_blocks if bp is not None else None,
        "tensor_parallel": core.tensor_parallel,
        # the RESOLVED speculative window (0 when speculation was not
        # requested or not viable): it shapes the verify program's
        # [num_slots, spec_k+1] operands, so a spec_k=2 engine must not
        # warm-load a spec_k=4 store's verify artifact
        "spec_k": core.spec_k if core.spec_on else 0,
    }


def _wrap_call(exported, donate: Tuple[int, ...], mesh=None) -> Callable:
    """Re-wrap a deserialized program as a dispatchable callable.
    Executing ``exported.call`` never re-traces the original Python
    body (so no trace counter can tick); the jit wrapper restores the
    donation contract the traced program had, keeping pool memory a
    single allocation on the warm path too.

    ``mesh``: programs exported for an N-device mesh refuse to run when
    any operand lives on fewer devices ("exported for N devices and is
    called in a context with 1"), and the engine's host-built operands
    (token ids, positions, sampling knobs) are exactly that.  The shim
    replicates any operand not already spanning the mesh; the sharded
    slabs (which include every donated operand) pass through untouched,
    so donation still lands on the real buffers."""
    if donate:
        fn = jax.jit(exported.call, donate_argnums=donate)
    else:
        fn = jax.jit(exported.call)
    if mesh is None or mesh.size <= 1:
        return fn
    from .tp import replicated

    def call(*args):
        placed = tuple(
            a if (isinstance(a, jax.Array)
                  and len(a.sharding.device_set) == mesh.size)
            else replicated(a, mesh)
            for a in args)
        return fn(*placed)

    return call


# ---------------------------------------------------------------- store
class AOTStore:
    """Reader handle over a PUBLISHED store directory.

    Pure host state plus lazy artifact reads; share one instance across
    every engine in a fleet (loads are independent).  Pair ``open`` with
    ``close`` (registered ResourcePair).  ``faults`` is the chaos hook:
    ``aot_store_corrupt`` fires inside the CRC read path so the suite
    can prove a rotted artifact degrades the engine to tracing."""

    def __init__(self, path: str, index: Dict[str, Any], faults=None):
        self.path = path
        self._index = index
        self.faults = faults
        self._closed = False

    # ------------------------------------------------------- lifecycle
    @classmethod
    def open(cls, path: str, faults=None) -> "AOTStore":
        """Open a published store.  Raises :class:`AOTStoreError` when
        no index was ever published (a crashed build leaves objects but
        no index — that is the atomicity contract, not corruption)."""
        index_path = os.path.join(path, INDEX_NAME)
        if not os.path.exists(index_path):
            raise AOTStoreError(
                f"no published AOT store at {path!r} (missing "
                f"{INDEX_NAME}; a build that crashed before publish "
                f"leaves no index)")
        try:
            with open(index_path, "r", encoding="utf-8") as f:
                index = json.load(f)
        except (OSError, ValueError) as e:
            raise AOTStoreError(
                f"unreadable AOT store index at {index_path!r}: "
                f"{e!r}") from e
        if index.get("version") != STORE_VERSION:
            raise AOTStoreError(
                f"AOT store version skew: index version "
                f"{index.get('version')!r}, runtime expects "
                f"{STORE_VERSION}")
        return cls(path, index, faults=faults)

    def close(self) -> None:
        """Release the handle (idempotent; loads after close raise)."""
        self._closed = True

    # --------------------------------------------------------- queries
    @property
    def fingerprint(self) -> str:
        return self._index.get("fingerprint", "")

    @property
    def widths(self) -> Tuple[int, ...]:
        """The committed prefill bucket-width set recorded at build."""
        return tuple(self._index.get("widths", ()))

    @property
    def context(self) -> Dict[str, Any]:
        return dict(self._index.get("context", {}))

    @property
    def build_seconds(self) -> float:
        """Total builder export time across artifacts (observability:
        the ``aot.build_s`` gauge an attaching engine republishes)."""
        return float(sum(e.get("build_s", 0.0)
                         for e in self._index.get("programs", {}).values()))

    def programs(self) -> Dict[str, Dict[str, Any]]:
        return dict(self._index.get("programs", {}))

    def has(self, name: str) -> bool:
        return name in self._index.get("programs", {})

    # ----------------------------------------------------------- loads
    def load(self, name: str):
        """Deserialize program ``name`` (CRC-verified).  Raises
        :class:`AOTStoreError` on a missing entry or corrupt artifact —
        the ENGINE turns that into a degradation event, never a crash."""
        if self._closed:
            raise AOTStoreError("AOT store handle is closed")
        entry = self._index.get("programs", {}).get(name)
        if entry is None:
            raise AOTStoreError(
                f"program {name!r} not in store index (have: "
                f"{sorted(self._index.get('programs', {}))})")
        payload = self._read_object(entry["object"])
        try:
            return _jx.deserialize(bytearray(payload))
        except Exception as e:
            raise AOTStoreError(
                f"artifact {name!r} failed to deserialize (jax/jaxlib "
                f"skew?): {e!r}") from e

    def load_call(self, name: str, donate: Sequence[int] = (),
                  mesh=None) -> Callable:
        """:meth:`load` + the donation-restoring jit re-wrap — what the
        engine installs as its program handle.  Pass the engine's mesh
        for tensor-parallel programs so host-built operands are
        replicated up to the export's device count (see
        :func:`_wrap_call`)."""
        return _wrap_call(self.load(name), tuple(donate), mesh=mesh)

    def _read_object(self, obj: str) -> bytes:
        path = os.path.join(self.path, OBJECTS_DIR, obj + ".aot")
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError as e:
            raise AOTStoreError(
                f"artifact object {obj!r} unreadable: {e!r}") from e
        if self.faults is not None:
            # chaos: pretend the frame rotted — same code path a real
            # flipped bit takes below
            if self.faults.check("aot_store_corrupt") is not None:
                raise AOTStoreError(
                    f"artifact object {obj!r} corrupt (injected)")
        if len(raw) < _HEADER.size:
            raise AOTStoreError(
                f"artifact object {obj!r} truncated ({len(raw)} bytes)")
        n, crc = _HEADER.unpack_from(raw)
        if n > _MAX_PAYLOAD or len(raw) != _HEADER.size + n:
            raise AOTStoreError(
                f"artifact object {obj!r} corrupt: framed length {n}, "
                f"file holds {len(raw) - _HEADER.size} payload bytes")
        payload = raw[_HEADER.size:]
        if zlib.crc32(payload) != crc:
            raise AOTStoreError(
                f"artifact object {obj!r} corrupt: CRC mismatch")
        return payload

    # ------------------------------------------------------- authoring
    @classmethod
    def create(cls, path: str, *, context: Dict[str, Any],
               plane: Dict[str, Any],
               widths: Sequence[int]) -> "AOTStoreWriter":
        """Start a build into ``path``.  Pair with ``publish()`` on
        success or ``discard()`` on every abort path (registered
        ResourcePair) — nothing is visible to readers until publish."""
        return AOTStoreWriter(path, context=context, plane=plane,
                              widths=widths)


class AOTStoreWriter:
    """One in-flight build: content-addressed objects land immediately
    (a crash leaves only unreferenced garbage), the index lands whole
    at :meth:`publish` — tmp-write + fsync + ``os.replace``, the
    journal's torn-tail discipline applied to a single file."""

    def __init__(self, path: str, *, context: Dict[str, Any],
                 plane: Dict[str, Any], widths: Sequence[int]):
        self.path = path
        self.context = _canon(context)
        self.fingerprint = aot_fingerprint(context)
        self.plane = plane
        self.widths = tuple(int(w) for w in widths)
        self._programs: Dict[str, Dict[str, Any]] = {}
        self._written: List[str] = []
        self._done = False
        os.makedirs(os.path.join(path, OBJECTS_DIR), exist_ok=True)

    def add(self, name: str, exported, *, build_s: float = 0.0) -> None:
        """Serialize + CRC-frame one program under leg key ``name``
        (``prefill:w<width>`` / ``decode:<path>`` / ``gather`` /
        ``scatter``)."""
        if self._done:
            raise AOTStoreError("writer already published/discarded")
        payload = bytes(exported.serialize())
        obj = hashlib.sha256(payload).hexdigest()
        obj_path = os.path.join(self.path, OBJECTS_DIR, obj + ".aot")
        frame = _HEADER.pack(len(payload), zlib.crc32(payload)) + payload
        tmp = obj_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(frame)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, obj_path)
        self._written.append(obj_path)
        counter = name.split(":", 1)[0]
        manifest_ids = list(self.plane.get(counter, {}).get("programs",
                                                           []))
        self._programs[name] = {
            "object": obj,
            "bytes": len(payload),
            "counter": counter,
            "manifest_programs": manifest_ids,
            "build_s": round(float(build_s), 6),
        }

    def _missing(self) -> List[str]:
        """Manifest program ids (by counter leg) the build has not
        covered — publish refuses while this is non-empty."""
        covered = {e["counter"] for e in self._programs.values()}
        missing: List[str] = []
        for counter in sorted(self.plane):
            if counter == "prefill":
                for w in self.widths:
                    if f"prefill:w{w}" not in self._programs:
                        missing.append(f"prefill:w{w}")
            elif counter == "decode":
                if not any(n.startswith("decode:")
                           for n in self._programs):
                    missing.append("decode:<path>")
            elif counter == "verify":
                # the STATIC plane always carries the verify counter
                # (the program exists in the source), but a spec_k=0
                # build has no verify program to export — completeness
                # is keyed on the store's resolved spec_k
                if not self.context.get("spec_k"):
                    continue
                if not any(n.startswith("verify:")
                           for n in self._programs):
                    missing.append("verify:<path>")
            elif counter not in covered:
                missing.append(counter)
        return missing

    def publish(self) -> Dict[str, Any]:
        """Completeness-check against the manifest plane, then publish
        atomically.  Refuses (store stays unpublished) when any manifest
        program id is missing or the manifest classifies a plane program
        unbounded — an unbounded key space cannot be enumerated, so an
        AOT store over it would be a lie."""
        if self._done:
            raise AOTStoreError("writer already published/discarded")
        for counter, entry in sorted(self.plane.items()):
            if entry.get("key_space") == "unbounded":
                raise AOTStoreError(
                    f"refusing to publish: manifest classifies "
                    f"{counter!r} UNBOUNDED ({entry.get('programs')}); "
                    f"an unbounded program set cannot be AOT-enumerated")
        missing = self._missing()
        if missing:
            raise AOTStoreError(
                f"refusing to publish: store misses manifest programs "
                f"{missing} (plane counters: {sorted(self.plane)}, "
                f"committed widths: {list(self.widths)})")
        index = {
            "version": STORE_VERSION,
            "fingerprint": self.fingerprint,
            "context": self.context,
            "widths": list(self.widths),
            "plane": {c: {"upper_bound": e.get("upper_bound"),
                          "key_space": e.get("key_space"),
                          "programs": list(e.get("programs", []))}
                      for c, e in sorted(self.plane.items())},
            "programs": self._programs,
            "built_unix": round(time.time(), 3),
        }
        index_path = os.path.join(self.path, INDEX_NAME)
        tmp = index_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(index, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, index_path)
        self._done = True
        return index

    def discard(self) -> None:
        """Abort: drop every object this writer wrote (idempotent).  A
        previously published index — if this was a rebuild into an
        existing store — is left untouched."""
        self._done = True
        for p in self._written:
            try:
                os.remove(p)
            except OSError:
                pass
        self._written = []


# -------------------------------------------------------------- builder
def _default_manifest() -> Dict[str, Any]:
    """The same manifest the CLI's ``graftlint --manifest`` emits,
    built through the shared library entry point over the repo scope."""
    from ..tools.analysis import build_manifest_for_paths
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    scope = [os.path.join(root, p)
             for p in ("paddle_tpu", "scripts")]
    return build_manifest_for_paths(scope, root=root)


def _on_mesh(core, x):
    """Replicate a host-built example arg onto the engine's mesh so a
    tensor-parallel export sees the same device assignment the sharded
    slabs carry (single-chip engines pass through)."""
    if core.mesh is None:
        return x
    from .tp import replicated
    return replicated(x, core.mesh)


def _staging_example(core):
    """Example prefill staging rows, built through the SAME compiled
    zero-staging program ``_begin_prefill`` uses — identical shapes,
    dtypes and (under tp) shardings to the runtime operands."""
    with core._mesh_scope():
        return core._build_staging_init_fn()()


def _baked(program, donate: Tuple[int, ...]) -> Callable:
    """The engine's ``program`` with its bound weight operand captured
    instead of passed: a stored artifact is self-contained (weights
    compiled in as constants — its point, and its size limit), and
    keeps the ``(ks, vs, ...)`` operand list the load path dispatches."""
    # graftlint: disable-next=recompile-hazard -- one-shot export: each wrapper is traced exactly once by jax.export and then dropped
    return jax.jit(lambda *operands: program(*operands),
                   donate_argnums=donate)


def _export_programs(core, writer: AOTStoreWriter) -> None:
    """Trace + AOT-lower the full manifest program set of ``core``:
    one prefill per committed bucket width, the ONE decode at the
    resolved path, the gather and the scatter.  Example operands are
    the engine's real device state (plus replicated host scalars), so
    exported shardings match what the runtime will pass."""
    ks, vs = _staging_example(core)
    prefill = _baked(core._build_prefill_fn(), (0, 1))
    pos = _on_mesh(core, jnp.asarray(0, jnp.int32))
    for w in writer.widths:
        t0 = time.perf_counter()
        ids = _on_mesh(core, jnp.zeros((1, w), jnp.int32))
        with core._mesh_scope():
            exported = _jx.export(prefill)(ks, vs, ids, pos, pos)
        writer.add(f"prefill:w{w}", exported,
                   build_s=time.perf_counter() - t0)

    t0 = time.perf_counter()
    decode = _baked(core._build_decode_fn(), (0, 1))
    n = core.num_slots
    vocab = int(core.model.cfg.vocab_size)
    sampling = (_on_mesh(core, jnp.tile(jax.random.PRNGKey(0)[None],
                                        (n, 1))),
                _on_mesh(core, jnp.zeros((n,), bool)),
                _on_mesh(core, jnp.ones((n,), jnp.float32)),
                _on_mesh(core, jnp.zeros((n,), jnp.int32)),
                _on_mesh(core, jnp.ones((n,), jnp.float32)),
                _on_mesh(core, jnp.ones((n, vocab), bool)))
    args = (core.pool.ks, core.pool.vs, core.pool.seq_pos,
            _on_mesh(core, jnp.zeros((n,), jnp.int32)),
            *sampling)
    with core._mesh_scope():
        exported = _jx.export(decode)(*args)
    writer.add(f"decode:{core.decode_path}", exported,
               build_s=time.perf_counter() - t0)

    if core.spec_on:
        # the ONE verify program: same operands as decode plus the
        # fixed-shape draft window (the engine keys the leg on the
        # decode path, exactly like decode itself)
        t0 = time.perf_counter()
        verify = _baked(core._build_verify_fn(), (0, 1))
        vargs = args + (
            _on_mesh(core, jnp.zeros((n, core.spec_k), jnp.int32)),
            _on_mesh(core, jnp.zeros((n,), jnp.int32)))
        with core._mesh_scope():
            exported = _jx.export(verify)(*vargs)
        writer.add(f"verify:{core.decode_path}", exported,
                   build_s=time.perf_counter() - t0)

    bp = core.block_pool
    idx = _on_mesh(core, jnp.zeros((bp.blocks_per_row,), jnp.int32))
    t0 = time.perf_counter()
    with core._mesh_scope():
        exported = _jx.export(bp._build_load_fn())(bp.bks, bp.bvs, idx)
    writer.add("gather", exported, build_s=time.perf_counter() - t0)
    t0 = time.perf_counter()
    slot = _on_mesh(core, jnp.asarray(0, jnp.int32))
    with core._mesh_scope():
        exported = _jx.export(bp._build_store_fn())(
            bp.bks, bp.bvs, core.pool.ks, core.pool.vs, slot, idx)
    writer.add("scatter", exported, build_s=time.perf_counter() - t0)


# why a model that declares a recurrent state (kv_pool.
# recurrent_state_spec) is neither built into a store nor served from one
RECURRENT_STATE_REFUSAL = (
    "the AOT store's program signatures carry no recurrent state "
    "operands: a model that declares one is served by tracing")


def build_engine_store(path: str, core,
                       manifest: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """Build + publish an AOT store for ``core``'s configuration.

    ``core`` is a constructed (cold is fine — the build IS its trace)
    :class:`~paddle_tpu.serving.engine.EngineCore`; ``manifest`` is the
    graftprog manifest dict (``scripts/graftlint.py --manifest`` output
    or :func:`build_manifest_for_paths` — rebuilt over the repo scope
    when omitted).  The builder engine must have its prefix cache
    enabled: the manifest plane holds the gather/scatter programs, and
    publish refuses an incomplete store.  Returns the published index.
    """
    if core.pool.state_bytes_per_slot:
        raise AOTStoreError(RECURRENT_STATE_REFUSAL)
    if manifest is None:
        manifest = _default_manifest()
    plane = manifest.get("planes", {}).get(ENGINE_PLANE)
    if plane is None:
        raise AOTStoreError(
            f"manifest has no {ENGINE_PLANE} plane (planes: "
            f"{sorted(manifest.get('planes', {}))})")
    if core.block_pool is None:
        raise AOTStoreError(
            "builder engine has no prefix-cache block pool; the "
            "manifest plane includes the gather/scatter programs, so "
            "build with enable_prefix_cache=True")
    writer = AOTStore.create(path, context=engine_aot_context(core),
                             plane=plane, widths=core.warm_buckets())
    try:
        _export_programs(core, writer)
        return writer.publish()
    except BaseException:
        writer.discard()
        raise
