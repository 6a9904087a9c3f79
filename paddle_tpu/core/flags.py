"""Global flag registry.

TPU-native analogue of the reference's exported-gflags system
(``PADDLE_DEFINE_EXPORTED_*`` in ``paddle/fluid/platform/flags.cc`` and the
Python getter/setter bound through
``paddle/fluid/pybind/global_value_getter_setter.cc``): a process-wide,
typed, env-overridable key→value store readable and settable from Python via
``paddle_tpu.get_flags`` / ``paddle_tpu.set_flags``.

Flags are defined at import time by the subsystem that owns them (matching
the reference's "flags live at point of use" convention, e.g.
``FLAGS_pserver_max_async_call_num`` defined at the top of
``brpc_ps_client.cc``). Environment variables named ``FLAGS_<name>`` override
the default at definition time, mirroring gflags' env bootstrap.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Iterable, Optional

__all__ = [
    "define_flag",
    "get_flags",
    "set_flags",
    "flag",
    "GLOBAL_FLAGS",
]

_BOOL_TRUE = frozenset({"1", "true", "yes", "on"})
_BOOL_FALSE = frozenset({"0", "false", "no", "off"})


class _FlagRegistry:
    """Thread-safe typed flag store."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._values: Dict[str, Any] = {}
        self._types: Dict[str, type] = {}
        self._help: Dict[str, str] = {}
        self._callbacks: Dict[str, Callable[[Any], None]] = {}

    def define(
        self,
        name: str,
        default: Any,
        help: str = "",
        on_change: Optional[Callable[[Any], None]] = None,
    ) -> None:
        with self._lock:
            if name in self._values:
                # Re-definition keeps the first definition (module reload safety).
                return
            env = os.environ.get("FLAGS_" + name)
            value = default
            if env is not None:
                value = self._coerce(env, type(default), name)
            self._values[name] = value
            self._types[name] = type(default)
            self._help[name] = help
            if on_change is not None:
                self._callbacks[name] = on_change

    @staticmethod
    def _coerce(raw: Any, ty: type, name: str) -> Any:
        if ty is bool:
            if isinstance(raw, bool):
                return raw
            s = str(raw).strip().lower()
            if s in _BOOL_TRUE:
                return True
            if s in _BOOL_FALSE:
                return False
            raise ValueError(f"flag {name}: cannot parse bool from {raw!r}")
        if ty is int:
            return int(raw)
        if ty is float:
            return float(raw)
        if ty is str:
            return str(raw)
        return raw

    def get(self, name: str) -> Any:
        with self._lock:
            if name not in self._values:
                raise KeyError(f"unknown flag: {name!r}")
            return self._values[name]

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            if name not in self._values:
                raise KeyError(f"unknown flag: {name!r}")
            coerced = self._coerce(value, self._types[name], name)
            self._values[name] = coerced
            cb = self._callbacks.get(name)
        if cb is not None:
            cb(coerced)

    def names(self) -> Iterable[str]:
        with self._lock:
            return tuple(self._values)

    def describe(self, name: str) -> str:
        with self._lock:
            return self._help.get(name, "")


GLOBAL_FLAGS = _FlagRegistry()


def define_flag(
    name: str,
    default: Any,
    help: str = "",
    on_change: Optional[Callable[[Any], None]] = None,
) -> None:
    """Define a process-wide flag (``PADDLE_DEFINE_EXPORTED_*`` analogue)."""
    GLOBAL_FLAGS.define(name, default, help, on_change)


def flag(name: str) -> Any:
    """Read one flag value (hot-path helper)."""
    return GLOBAL_FLAGS.get(name)


def get_flags(names) -> Dict[str, Any]:
    """Read flags. Accepts a name or list of names; returns name→value."""
    if isinstance(names, str):
        names = [names]
    return {n: GLOBAL_FLAGS.get(n) for n in names}


def set_flags(kv: Dict[str, Any]) -> None:
    """Set flags from a dict, with type coercion and change callbacks."""
    for name, value in kv.items():
        GLOBAL_FLAGS.set(name, value)


# ---------------------------------------------------------------------------
# Core flags (subsystem-specific flags are defined by their owning modules).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf after each step.")
# (the RNG seed flag is defined by paddle_tpu.nn.layer, which owns the
# ambient RNG stream, so its on_change callback can reseed it directly)
# Cross-cutting chaos switch: read by BOTH the transport faultpoint sites
# (ps/rpc.py) and the HA harness (ps/ha.py), so it lives here rather than
# at either point of use. Format and actions: ps/faultpoints.py.
define_flag("ps_faultpoints", "",
            "arm PS fault-injection sites: 'site=action[:k=v]*[;...]' — "
            "actions delay-ms/drop-frame/close-socket/kill-shard/"
            "corrupt-epoch (ps/faultpoints.py; chaos testing only)")
