"""Global flag registry.

TPU-native analog of the reference's gflags system
(paddle/fluid/platform/flags.cc ``PADDLE_DEFINE_EXPORTED_*``; env bootstrap at
python/paddle/fluid/__init__.py:150).  Flags are defined in one place, can be
overridden by ``FLAGS_<name>`` environment variables at import, and
get/set at runtime via ``get_flags``/``set_flags``.
"""
from __future__ import annotations

import os
import threading

__all__ = ["define_flag", "get_flags", "set_flags", "flag"]

_lock = threading.Lock()
_registry: dict[str, dict] = {}     # guarded-by: _lock


def _coerce(value, proto):
    if isinstance(proto, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if isinstance(proto, int):
        return int(value)
    if isinstance(proto, float):
        return float(value)
    return value


def define_flag(name: str, default, help_str: str = ""):
    """Register a flag; env var ``FLAGS_<name>`` overrides the default."""
    with _lock:
        env = os.environ.get(f"FLAGS_{name}")
        value = _coerce(env, default) if env is not None else default
        _registry[name] = {"value": value, "default": default,
                           "help": help_str,
                           "explicit": env is not None}
    return value


def flag(name: str):
    """Read a flag's current value."""
    # lint-ok: trace-purity flags are static config by contract: a
    # trace-time read (e.g. kernel selection) intentionally freezes
    # the value into that compile
    # lint-ok: lock-discipline eager-op hot path: one GIL-atomic dict
    # lookup of a value set_flags replaces atomically; a lock here
    # would serialize every op dispatch
    return _registry[name]["value"]


def get_flags(names=None):
    with _lock:
        if names is None:
            names = list(_registry)
        if isinstance(names, str):
            names = [names]
        return {n: _registry[n]["value"] for n in names}


def set_flags(mapping: dict):
    with _lock:
        for name, value in mapping.items():
            if name.startswith("FLAGS_"):
                name = name[len("FLAGS_"):]
            if name not in _registry:
                raise KeyError(f"unknown flag: {name}")
            _registry[name]["value"] = _coerce(value, _registry[name]["default"])
            _registry[name]["explicit"] = True


# --- core flags (subset of the reference's 59, TPU-relevant ones) -----------
define_flag("check_nan_inf", False, "scan op outputs for NaN/Inf (debug)")
define_flag("benchmark", False, "synchronize and time each op")
define_flag("eager_op_jit", False, "jit-cache eager per-op execution")
define_flag("use_bf16_matmul", True, "prefer bf16 inputs on MXU matmuls")
define_flag("seed", 0, "global random seed (0 = nondeterministic)")
define_flag("log_level", 0, "framework VLOG-style verbosity")

# --- allocator knobs (reference: FLAGS_fraction_of_gpu_memory_to_use +
# FLAGS_allocator_strategy, allocator_facade.h:43).  On TPU the XLA/PJRT
# client owns allocation; these flags configure IT via its env contract,
# so they must be set before first device use. ----------------------------
define_flag("fraction_of_device_memory_to_use", 0.0,
            "0 = backend default; else sets XLA_PYTHON_CLIENT_MEM_FRACTION")
define_flag("allocator_strategy", "auto_growth",
            "'auto_growth' (XLA default, preallocate off) or 'preallocate'")


def apply_allocator_flags():
    """Push the allocator flags into the XLA client env (no-op after the
    backend initialized — call before first device use, as the reference
    requires for its allocator strategy).

    Only flags the user EXPLICITLY set (set_flags or FLAGS_* env) touch
    the client env: a default-valued flag must never clobber the user's
    own XLA_PYTHON_CLIENT_* variables at import."""
    import os

    with _lock:
        frac_explicit = _registry["fraction_of_device_memory_to_use"]["explicit"]
        strategy_explicit = _registry["allocator_strategy"]["explicit"]
    if frac_explicit:
        frac = flag("fraction_of_device_memory_to_use")
        if frac and frac > 0:
            os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(frac)
        else:
            os.environ.pop("XLA_PYTHON_CLIENT_MEM_FRACTION", None)
    if strategy_explicit:
        strategy = flag("allocator_strategy")
        if strategy == "preallocate":
            os.environ["XLA_PYTHON_CLIENT_PREALLOCATE"] = "true"
        elif strategy == "auto_growth":   # default: clear the override
            os.environ.pop("XLA_PYTHON_CLIENT_PREALLOCATE", None)
        else:
            raise ValueError(f"unknown allocator_strategy {strategy!r}")


apply_allocator_flags()
