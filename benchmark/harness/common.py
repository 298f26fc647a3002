"""What both phases share: the device check, the compile counter, the
environment a child runs in."""

from __future__ import annotations

import json
import os
import sys
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(Exception):
    pass


def child_env(root: str) -> dict:
    """The environment of a phase: the compile cache at a fixed path
    inside the checkout (the program's own rule, runtime/compile_cache.py,
    honours JAX_COMPILATION_CACHE_DIR), every program cached however
    short its compile, and the repo importable."""
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(root, ".jax-compile-cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("PYTHONHASHSEED", "0")   # same dict and set order every run
    env["PYTHONPATH"] = os.pathsep.join(
        [root] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def device_info(chips: int, require_chip: bool) -> dict:
    """The device as JAX reports it; NoChip unless it is a TPU with at
    least the chips the cell asks for."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if require_chip and (info["platform"] != "tpu" or info["count"] < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); jax reports "
                     f"{info['count']} x {info['platform']} "
                     f"({info['kind']})")
    info["count"] = min(info["count"], chips) if require_chip else info["count"]
    return info


def memory_peak(n_devices: int) -> int:
    """Peak bytes in use on the fullest of the first `n_devices` chips
    (0 where the backend does not say, as on the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n_devices]]
    return int(max(peaks)) if peaks else 0


class CompileCounter:
    """Every backend compilation (or load from the persistent cache) this
    process makes, with the wall-clock instant it ended at."""

    def __init__(self):
        import jax

        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.time(), duration))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


def trace_options():
    """Device operations and the runtime's own host events; not the
    Python tracer, which slows the engine's host loop severalfold."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def say(line: dict, device: dict | None = None) -> None:
    """One line of the run's own output; every line names the device."""
    if device is not None:
        line = {**line, "device": {k: device[k] for k in
                                   ("platform", "kind", "count")}}
    print(json.dumps(line), flush=True)


def write_json(path: str, payload) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh)
    os.replace(tmp, path)


def fail(message: str, code: int = 1):
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    sys.exit(code)
