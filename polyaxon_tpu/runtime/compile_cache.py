"""Where the persistent XLA compilation cache lives.

Every restart of a run or a server repays full XLA compilation before
its first step; JAX's persistent cache turns the second compile into a
disk load, provided the directory is the same both times: a cache
that moves is never found again. One rule, for training, serving and
every bench script alike:

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of it is all
   there is. Nothing here touches the config, whatever else is set, so
   a cache placed from outside (a CI volume, the operator's shell) is
   the one in use.
2. Unset, and the backend is a TPU: one fixed directory inside the
   checkout, ``<repo>/.jax-compile-cache`` — never a temp name, pid or
   timestamp, so the next process finds it.
3. Unset, any other backend: off. XLA:CPU's AOT reload of sharded
   executables is unreliable on oversubscribed hosts (tests/conftest.py
   documents cache hits hanging at collective rendezvous).

``enable()`` is called by each entry point that compiles
(``run_jaxjob``, ``ServingServer``); it initializes the backend, so it
belongs to the process that owns the device.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)

ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax-compile-cache")


# Requests this process made of the persistent cache, as jax's own
# monitoring events count them ("compile_requests_use_cache" = asked,
# "cache_hits" = answered from disk).
_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
           "/jax/compilation_cache/cache_hits": "hits"}
_counts = {"requests": 0, "hits": 0}
_listening = False


def _count(event: str, **_) -> None:
    if event in _EVENTS:
        _counts[_EVENTS[event]] += 1


def stats() -> dict:
    """The cache directory in effect (None = off) and what this process
    has asked of it so far: a run can then say whether a short compile
    was a disk load."""
    import jax

    return {"dir": jax.config.jax_compilation_cache_dir, **_counts}


def enable() -> Optional[str]:
    """Apply the rule above; returns the directory in use, or None."""
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    placed = os.environ.get(ENV_JAX_CACHE_DIR)
    if placed:
        return placed
    if jax.default_backend() != "tpu":
        return None
    if jax.config.jax_compilation_cache_dir != REPO_CACHE_DIR:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
        # jax initializes its file cache at most once per process, and
        # a compile that ran before the directory was set latches it to
        # "disabled"; reset so the setting is read.
        compilation_cache.reset_cache()
        logger.info("persistent compilation cache at %s", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
