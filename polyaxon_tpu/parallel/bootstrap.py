"""Multi-host process-group bootstrap over DCN.

TPU-native replacement for the reference's rendezvous env contract
(SURVEY.md §2c [K]: Kubeflow operators inject ``TF_CONFIG`` /
``MASTER_ADDR`` / ``RANK`` and MPIJob runs ``mpirun`` with hostfiles):
here the launch plan injects ``POLYAXON_TPU_COORDINATOR`` /
``POLYAXON_TPU_NUM_PROCESSES`` / ``POLYAXON_TPU_PROCESS_ID`` (discovered
by the tpu_metadata init phase on real TPU-VMs [B]) and every process
calls ``jax.distributed.initialize`` — after which XLA collectives ride
ICI within a slice and DCN across slices with no NCCL anywhere.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Optional

logger = logging.getLogger(__name__)

ENV_COORDINATOR = "POLYAXON_TPU_COORDINATOR"
ENV_NUM_PROCESSES = "POLYAXON_TPU_NUM_PROCESSES"
ENV_PROCESS_ID = "POLYAXON_TPU_PROCESS_ID"
ENV_LOCAL_DEVICE_IDS = "POLYAXON_TPU_LOCAL_DEVICE_IDS"


@dataclass
class ProcessGroup:
    coordinator: Optional[str]
    num_processes: int
    process_id: int
    initialized: bool

    @property
    def is_multiprocess(self) -> bool:
        return self.num_processes > 1


def read_env_contract(env: Optional[dict[str, str]] = None) -> ProcessGroup:
    env = dict(os.environ if env is None else env)
    return ProcessGroup(
        coordinator=env.get(ENV_COORDINATOR),
        num_processes=int(env.get(ENV_NUM_PROCESSES, "1")),
        process_id=int(env.get(ENV_PROCESS_ID, "0")),
        initialized=False,
    )


def initialize(group: Optional[ProcessGroup] = None) -> ProcessGroup:
    """Idempotently bootstrap the JAX process group from the env contract.

    Single-process (the common local one-host case) is a no-op; multi-
    process calls ``jax.distributed.initialize`` against the coordinator
    over DCN.
    """
    group = group or read_env_contract()
    if not group.is_multiprocess:
        group.initialized = True
        return group
    if not group.coordinator:
        raise RuntimeError(
            f"{ENV_NUM_PROCESSES}={group.num_processes} but {ENV_COORDINATOR} is unset; "
            "the launch plan must inject the coordinator address"
        )
    import jax

    local_ids = os.environ.get(ENV_LOCAL_DEVICE_IDS)
    kwargs = {}
    if local_ids:
        kwargs["local_device_ids"] = [int(i) for i in local_ids.split(",")]
    jax.distributed.initialize(
        coordinator_address=group.coordinator,
        num_processes=group.num_processes,
        process_id=group.process_id,
        **kwargs,
    )
    logger.info(
        "jax.distributed initialized: process %d/%d via %s",
        group.process_id, group.num_processes, group.coordinator,
    )
    group.initialized = True
    return group
