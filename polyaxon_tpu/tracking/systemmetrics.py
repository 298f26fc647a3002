"""System-metrics processors: host (psutil) + TPU (libtpu / device API).

Parity: traceml's processors thread samples psutil + NVML every N s
(SURVEY.md §5.1 [K]); the TPU build replaces NVML with two layers of
TPU metrics (SURVEY §2a note 3):

- ``device.memory_stats()`` (PJRT) — HBM usage, portable everywhere;
- the **libtpu monitoring SDK** (``libtpu.sdk.tpumonitoring``) — duty
  cycle, TensorCore utilization, ICI link health, throttle score —
  probed behind import guards and a one-time availability latch, so
  hosts without real TPU hardware (or with an older libtpu) degrade
  silently to psutil + HBM.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

import psutil

logger = logging.getLogger(__name__)


def host_metrics() -> dict[str, float]:
    vm = psutil.virtual_memory()
    disk = psutil.disk_usage("/")
    out = {
        "cpu_percent": psutil.cpu_percent(interval=None),
        "memory_used_gb": vm.used / 2**30,
        "memory_percent": vm.percent,
        "disk_used_percent": disk.percent,
    }
    try:
        load1, _, _ = psutil.getloadavg()
        out["load_1m"] = load1
    except OSError:
        pass
    return out


def tpu_metrics() -> dict[str, float]:
    """Best-effort per-device metrics from the PJRT client; keys are
    ``tpu<i>_*``. Empty off-TPU or when the client exposes no stats.
    Initializes the backend, so it is sampled only in the process that
    runs the job (``runtime/launch.py``'s lead, or user code holding
    the tracking ``Run``) — never in the agent, which must stay off
    the chip its gangs need."""
    out: dict[str, float] = {}
    try:
        import jax

        for i, dev in enumerate(jax.local_devices()):
            if dev.platform != "tpu":
                continue
            try:
                stats = dev.memory_stats() or {}
            except Exception as exc:
                logger.debug("tpu%d memory_stats unavailable: %s", i, exc)
                continue
            in_use = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
            if in_use is not None:
                out[f"tpu{i}_hbm_used_gb"] = in_use / 2**30
            if in_use is not None and limit:
                out[f"tpu{i}_hbm_percent"] = 100.0 * in_use / limit
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                out[f"tpu{i}_hbm_peak_gb"] = peak / 2**30
    except Exception as exc:
        logger.debug("tpu metrics sample failed: %s", exc)
    return out


# libtpu metric name → emitted key prefix. Values parse per-chip where
# the SDK reports lists. Unavailable metrics (older libtpu, no real
# chip) are skipped per-name; a failing SDK disables itself once.
_LIBTPU_METRICS = {
    "duty_cycle_pct": "tpu{i}_duty_cycle_pct",
    "tensorcore_util": "tpu{i}_tensorcore_util",
    "ici_link_health": "tpu{i}_ici_link_health",
    "tpu_throttle_score": "tpu{i}_throttle_score",
}
_libtpu_state: dict = {"disabled": False}


def libtpu_metrics() -> dict[str, float]:
    """Duty cycle / TensorCore utilization / ICI link health via the
    libtpu monitoring SDK — the metrics NVML provides upstream (SURVEY
    §5.1). Best-effort: returns {} without real TPU hardware. A raising
    SDK latches disabled so the sampler never retries a dead surface;
    per-metric failures (unsupported on this libtpu) skip that metric
    only."""
    out: dict[str, float] = {}
    if _libtpu_state["disabled"]:
        return out
    try:
        from libtpu.sdk import tpumonitoring
    except Exception:
        _libtpu_state["disabled"] = True
        return out
    try:
        supported = _libtpu_state.get("supported")
        if supported is None:
            supported = set(tpumonitoring.list_supported_metrics())
            _libtpu_state["supported"] = supported
    except Exception:
        _libtpu_state["disabled"] = True
        return out
    for name, key_fmt in _LIBTPU_METRICS.items():
        if name not in supported:
            continue
        try:
            data = tpumonitoring.get_metric(name).data()
        except Exception as exc:
            # snapshot unavailable right now; not fatal
            logger.debug("libtpu metric %s unavailable: %s", name, exc)
            continue
        for i, raw in enumerate(data if isinstance(data, (list, tuple))
                                else [data]):
            try:
                out[key_fmt.format(i=i)] = float(raw)
            except (TypeError, ValueError):
                continue
    return out


class SystemMetricsMonitor:
    """Background sampler thread; emits through a callback (the tracking
    Run wires it to ``log_metrics(kind='system')``)."""

    def __init__(
        self,
        emit: Callable[[dict[str, float]], None],
        interval_seconds: float = 10.0,
        include_tpu: bool = True,
    ):
        self.emit = emit
        self.interval = interval_seconds
        self.include_tpu = include_tpu
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def sample(self) -> dict[str, float]:
        metrics = host_metrics()
        if self.include_tpu:
            device = tpu_metrics()
            metrics.update(device)
            if device:
                # Only with a TPU attached to THIS process: loading
                # libtpu anywhere else contends for its one-process lock.
                metrics.update(libtpu_metrics())
        return metrics

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.emit(self.sample())
            except Exception as exc:
                # sampling must never kill the training process
                logger.debug("system metrics sample dropped: %s", exc)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop, name="plx-sysmetrics", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
