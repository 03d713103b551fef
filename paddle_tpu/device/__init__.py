"""Device + memory-stats facade.

Reference: python/paddle/device/ — paddle.device.cuda.max_memory_allocated
etc., backed by paddle/fluid/memory/stats.cc (DEVICE_MEMORY_STAT macros)
over the allocator facade (SURVEY.md §2.1 "Memory/allocators", §5
"Metrics/logging").

TPU-native: allocation is PJRT's job; the stats come from
``Device.memory_stats()`` (bytes_in_use, peak_bytes_in_use, ...).  The
facade keeps the reference's function names and byte semantics.  The
``cuda`` alias namespace exists so ported code calling
``paddle.device.cuda.max_memory_allocated()`` keeps working on TPU.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["get_device", "set_device", "device_count", "is_compiled_with_cuda",
           "memory_allocated", "memory_reserved", "max_memory_allocated",
           "max_memory_reserved", "memory_stats", "empty_cache", "cuda",
           "synchronize", "enable_compile_cache"]

_current = None


def _dev(device=None):
    devs = jax.devices()
    if device is None:
        return devs[0]
    if isinstance(device, int):
        return devs[device]
    if isinstance(device, str) and ":" in device:
        return devs[int(device.rsplit(":", 1)[1])]
    return devs[0]


def get_device() -> str:
    d = _dev()
    return f"{d.platform}:{d.id}"


def set_device(device: str) -> str:
    """Parity shim: JAX places by sharding, not a global current device;
    records the choice for get_device symmetry."""
    global _current
    _current = device
    return device


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def memory_stats(device=None) -> dict:
    """Raw PJRT stats dict ({} on backends that expose none, e.g. CPU)."""
    try:
        return dict(_dev(device).memory_stats() or {})
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    """Reference: paddle.device.cuda.memory_allocated — live bytes."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Reference: paddle.device.cuda.max_memory_allocated — peak bytes."""
    s = memory_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("pool_bytes", 0)))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_bytes_reserved",
                     s.get("largest_alloc_size", 0)))


def empty_cache() -> None:
    """Parity no-op: PJRT owns its pools (documented deviation)."""


def synchronize(device=None) -> None:
    """Block host until device work completes (reference:
    paddle.device.synchronize)."""
    jax.effects_barrier()
    for x in jax.live_arrays():
        try:
            x.block_until_ready()
        except Exception:
            pass


# the checkout root (the directory that holds the package): where the
# persistent compile cache lives when the environment names no other
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
    itself and this sets nothing; otherwise the cache is the fixed,
    git-ignored ``<checkout>/.jax_cache``.  A cache that moves never
    hits, so the directory is never a temp name, a pid or a timestamp —
    and no other code path sets one."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE


class _CudaNamespace:
    """paddle.device.cuda.* alias surface for ported code."""
    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)
    empty_cache = staticmethod(empty_cache)
    synchronize = staticmethod(synchronize)

    @staticmethod
    def device_count():
        return device_count()


cuda = _CudaNamespace()

# custom-device plugin seam (reference: paddle/phi/backends/custom/) —
# registry surface over PJRT plugins; see device/custom.py for the stance
from . import custom  # noqa: E402
from .custom import (  # noqa: E402
    CustomPlace, register_custom_device, unregister_custom_device,
    get_all_custom_device_type, is_compiled_with_custom_device,
    custom_device_count)

__all__ += ["custom", "CustomPlace", "register_custom_device",
            "unregister_custom_device", "get_all_custom_device_type",
            "is_compiled_with_custom_device", "custom_device_count"]


class Stream:
    """Reference: paddle.device.Stream.  XLA owns stream scheduling (the
    compiler orders device work); this facade keeps the API so ported
    code runs — wait_event/wait_stream/synchronize order HOST progress
    the way record/wait order device streams in the reference."""

    def __init__(self, device=None, priority: int = 2):
        self.device = device
        self.priority = priority

    def synchronize(self):
        synchronize(self.device)

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def record_event(self, event=None):
        event = event or Event()
        event.record(self)
        return event

    def query(self) -> bool:
        synchronize(self.device)
        return True


class Event:
    """Reference: paddle.device.Event over the stream facade."""

    def __init__(self, device=None, enable_timing: bool = False,
                 blocking: bool = False, interprocess: bool = False):
        pass

    def record(self, stream=None):
        # XLA dispatch is synchronous from the host's perspective here;
        # query()/synchronize() need no recorded marker
        pass

    def query(self) -> bool:
        return True

    def synchronize(self):
        synchronize()


import contextlib as _contextlib


@_contextlib.contextmanager
def stream_guard(stream):
    """Reference: paddle.device.stream_guard — ops issued in the guard run
    on the given stream.  XLA schedules streams itself; the guard keeps
    scope semantics (the stream is synchronized on exit, matching the
    reference's ordering guarantee at the guard boundary)."""
    try:
        yield stream
    finally:
        if stream is not None:
            stream.synchronize()


def current_stream(device=None) -> "Stream":
    return Stream(device)


def get_available_device():
    """Reference: paddle.device.get_available_device — every visible
    device, tagged the reference way (indices count PER PLATFORM, so a
    mixed cpu+tpu listing yields tpu:0/tpu:1, not global enumeration
    positions)."""
    import jax
    out = []
    per_platform = {}
    for d in jax.devices():
        i = per_platform.setdefault(d.platform, 0)
        per_platform[d.platform] = i + 1
        if d.platform == "cpu":
            if i == 0:           # reference lists the host cpu once
                out.append("cpu")
        else:
            out.append(f"{d.platform}:{i}")
    return out


def get_available_custom_device():
    """Reference: paddle.device.get_available_custom_device — ONLY
    plugin (custom) devices, not ordinary accelerators: each type
    registered via device.custom.register_custom_device is listed as
    ``type:i`` per device of its backing JAX platform."""
    import jax
    from .custom import _REGISTRY
    out = []
    for dev_type in sorted(_REGISTRY):
        try:
            n = len(jax.devices(_REGISTRY[dev_type]))
        except RuntimeError:
            n = 0
        out.extend(f"{dev_type}:{i}" for i in range(n))
    return out


__all__ += ["Stream", "Event", "stream_guard", "current_stream",
            "get_available_device", "get_available_custom_device"]


def get_all_device_type():
    """Reference: paddle.device.get_all_device_type — every device type
    the build supports."""
    import jax
    return sorted({d.platform for d in jax.devices()} | {"cpu"})


__all__ += ["get_all_device_type"]
