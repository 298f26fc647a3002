"""Small environment helpers shared by the CLI and runtime entrypoints."""

from __future__ import annotations

import os


def cpu_mesh_xla_flags(n_devices: int = 8, *,
                       watchdog_timeout_s: int = 600) -> None:
    """Point ``XLA_FLAGS`` at an ``n_devices`` virtual CPU mesh, with
    the collective-rendezvous watchdog sized for an oversubscribed
    host. Must run BEFORE any jax backend initializes (this module
    imports no jax).

    Two flags, both append-only and NEVER overriding an operator's
    explicit setting (XLA's repeated-flag parsing is last-wins, so we
    skip appending when the flag is already present):

    - ``--xla_force_host_platform_device_count=N``: the virtual mesh.
    - ``--xla_cpu_collective_call_terminate_timeout_seconds``: XLA:CPU
      CHECK-aborts the whole process when any device thread misses a
      collective rendezvous for 40 s; with N device threads sharing
      one physical core a straggler starves past that easily
      (reproduced standalone at seq 16k, 2026-08-01 — the former
      "full-suite segfault", see tests/conftest.py). 600 s keeps the
      watchdog as a deadlock backstop without killing slow-but-live
      programs.
    """
    flags = os.environ.get("XLA_FLAGS", "").split()
    if not any(f.startswith("--xla_force_host_platform_device_count")
               for f in flags):
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    if not any(f.startswith("--xla_cpu_collective_call_terminate_timeout")
               for f in flags):
        flags.append("--xla_cpu_collective_call_terminate_timeout_seconds"
                     f"={watchdog_timeout_s}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


# Latency-hiding scheduler pins for TPU runtimes (parallel/overlap.py
# owns the rationale and the per-compile compiler_options twin). These
# are libtpu flags: they go through LIBTPU_INIT_ARGS, NEVER XLA_FLAGS —
# XLA:CPU CHECK-aborts the whole process on any unknown XLA_FLAGS entry,
# and a CPU-only jaxlib does not know the xla_tpu_* family.
TPU_OVERLAP_INIT_ARGS: tuple[str, ...] = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion=true",
)


def tpu_overlap_libtpu_args() -> bool:
    """Pin the collective-overlap scheduler flags into
    ``LIBTPU_INIT_ARGS``. Must run BEFORE the TPU backend initializes.

    Same contract as :func:`cpu_mesh_xla_flags`: append-only, never
    overriding an operator's explicit setting (skip any flag whose key
    is already present), and gated on the runtime actually shipping
    libtpu (metadata probe only, no backend init) so a CPU-only image
    is untouched. Returns whether anything was pinned.
    """
    if not _libtpu_available():
        return False
    args = os.environ.get("LIBTPU_INIT_ARGS", "").split()
    appended = False
    for flag in TPU_OVERLAP_INIT_ARGS:
        key = flag.split("=", 1)[0]
        if not any(a.split("=", 1)[0] == key for a in args):
            args.append(flag)
            appended = True
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(args)
    return appended


def _libtpu_available() -> bool:
    """Whether a libtpu wheel is importable (metadata-only probe)."""
    try:
        import importlib.util

        return any(importlib.util.find_spec(name) is not None
                   for name in ("libtpu", "libtpu_nightly"))
    except Exception:  # noqa: BLE001 — unknown packaging: don't pin
        return False
