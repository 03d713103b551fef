"""Runtime flag registry.

Reference: Paddle's native gflags clone — paddle/utils/flags.h,
paddle/phi/core/flags.cc (``PHI_DEFINE_EXPORTED_*``), surfaced in Python as
``paddle.set_flags`` / ``paddle.get_flags``; ~300 ``FLAGS_*`` control
allocator strategy, cudnn determinism, nccl blocking wait, nan/inf checks...
(SURVEY.md §2.1 "Flags system", §5 "Config / flag system").

TPU-native version: a typed in-process registry with env-var override
(``FLAGS_<name>=...`` read at first access), no native code needed — XLA owns
the runtime knobs the reference's flags mostly configure.  Flags that map to
XLA/JAX settings apply them on set (see ``_APPLIERS``).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional

__all__ = ["define_flag", "set_flags", "get_flags", "flags"]


@dataclass
class _Flag:
    name: str
    default: Any
    type: type
    help: str
    value: Any = None
    from_env: bool = False


_REGISTRY: Dict[str, _Flag] = {}
_LOCK = threading.Lock()
_APPLIERS: Dict[str, Callable[[Any], None]] = {}


def _coerce(raw: str, typ: type) -> Any:
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return typ(raw)


def define_flag(name: str, default: Any, help: str = "",
                applier: Optional[Callable[[Any], None]] = None) -> None:
    with _LOCK:
        typ = type(default)
        fl = _Flag(name=name, default=default, type=typ, help=help)
        env = os.environ.get(f"FLAGS_{name}")
        if env is not None:
            fl.value = _coerce(env, typ)
            fl.from_env = True
        else:
            fl.value = default
        _REGISTRY[name] = fl
        if applier is not None:
            _APPLIERS[name] = applier
            applier(fl.value)


def set_flags(flags_: Dict[str, Any]) -> None:
    """Parity: ``paddle.set_flags({'FLAGS_check_nan_inf': 1})`` — accepts
    names with or without the FLAGS_ prefix."""
    for k, v in flags_.items():
        name = k[6:] if k.startswith("FLAGS_") else k
        with _LOCK:
            if name not in _REGISTRY:
                raise ValueError(f"unknown flag {k!r}")
            fl = _REGISTRY[name]
            fl.value = _coerce(str(v), fl.type) if not isinstance(v, fl.type) else v
        if name in _APPLIERS:
            _APPLIERS[name](_REGISTRY[name].value)


def get_flags(names: Iterable[str] | str) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    out = {}
    for k in names:
        name = k[6:] if k.startswith("FLAGS_") else k
        if name not in _REGISTRY:
            raise ValueError(f"unknown flag {k!r}")
        out[k] = _REGISTRY[name].value
    return out


class _FlagsNamespace:
    """Attribute access: ``flags.check_nan_inf``."""

    def __getattr__(self, name: str) -> Any:
        try:
            return _REGISTRY[name].value
        except KeyError:
            raise AttributeError(name) from None

    def __setattr__(self, name: str, value: Any) -> None:
        # write through to the registry: a plain instance attribute would
        # permanently shadow the flag for every later set_flags() call
        if name in _REGISTRY:
            set_flags({name: value})
        else:
            raise AttributeError(f"unknown flag {name!r}")


flags = _FlagsNamespace()


def _apply_debug_nans(v: bool) -> None:
    try:
        import jax
        jax.config.update("jax_debug_nans", bool(v))
    except Exception:
        pass


# Core flag set (TPU-meaningful subset of the reference's ~300).
define_flag("check_nan_inf", False,
            "Scan op outputs for NaN/Inf (reference: FLAGS_check_nan_inf -> "
            "nan_inf_utils_detail; here: jax_debug_nans + check_numerics "
            "wrappers)", applier=_apply_debug_nans)
define_flag("benchmark", False, "Print per-step timing in training loops")
define_flag("deterministic", True,
            "XLA on TPU is deterministic by default; flag kept for parity "
            "with FLAGS_cudnn_deterministic")
define_flag("default_dtype", "float32", "Default floating dtype")
define_flag("allocator_strategy", "xla",
            "Parity stub: device memory is managed by the XLA runtime "
            "(reference: auto_growth allocator)")
define_flag("log_level", "INFO", "Framework log level")
define_flag("use_pallas_attention", True,
            "Route scaled_dot_product_attention to the Pallas flash kernel "
            "on TPU when shapes allow")
define_flag("use_pallas_norm", True,
            "Route last-dim layer_norm (full weight+bias) to the fused "
            "Pallas kernel on TPU")
define_flag("pallas_routing", "auto",
            "Pallas-vs-XLA kernel routing: 'auto' follows the measured "
            "per-shape table (paddle_tpu/kernels/routing.py), 'always' "
            "forces every flag-enabled kernel, 'never' disables Pallas")
