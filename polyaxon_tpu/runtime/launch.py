"""Process entrypoint for compiled JAXJob runs.

The launch plan sets ``POLYAXON_JAXJOB_SPEC`` + the tracking/bootstrap
env contract; every gang process runs ``python -m
polyaxon_tpu.runtime.launch`` (SURVEY.md §3.3 in-pod stack, with the
main-process half owned by the framework instead of user code).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import traceback

from polyaxon_tpu.compiler.compile import ENV_JAXJOB_SPEC
from polyaxon_tpu.lifecycle import V1Statuses
from polyaxon_tpu.parallel import bootstrap
from polyaxon_tpu.polyflow.runs import V1JAXJob
from polyaxon_tpu.runtime.loop import run_jaxjob
from polyaxon_tpu.tracking.run import ENV_ARTIFACTS_PATH, ENV_RUN_UUID, Run

logger = logging.getLogger(__name__)


def main() -> int:
    # force=True: the module imports above pull in jax, whose absl
    # bridge may already have attached a root handler — without force,
    # basicConfig is a silent no-op and root stays at WARNING, so no
    # framework INFO line (mesh shape, bootstrap, step logs) ever
    # reaches the gang's log files.
    logging.basicConfig(
        level=os.environ.get("POLYAXON_LOG_LEVEL", "INFO"),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
        force=True,
    )
    from polyaxon_tpu.parallel import overlap

    # Pin the latency-hiding scheduler before the backend initializes
    # (bootstrap.initialize below) so collective overlap — and with it
    # the budgeted overlap_ratio floors — cannot silently regress with
    # a libtpu default flip. No-op off-TPU (parallel/overlap.py).
    overlap.pin_runtime_flags()
    spec_json = os.environ.get(ENV_JAXJOB_SPEC)
    if not spec_json:
        print(f"{ENV_JAXJOB_SPEC} is not set", file=sys.stderr)
        return 2
    job = V1JAXJob.from_dict(json.loads(spec_json))

    run_uuid = os.environ.get(ENV_RUN_UUID, "local")
    artifacts_dir = os.environ.get(ENV_ARTIFACTS_PATH) or os.path.join(
        os.getcwd(), ".plx-runs", run_uuid
    )
    os.makedirs(artifacts_dir, exist_ok=True)

    group = bootstrap.initialize()
    is_lead = group.process_id == 0

    tracking = None
    if is_lead:
        tracking = Run(run_uuid, artifacts_dir, collect_system_metrics=True)
        tracking.log_status(V1Statuses.RUNNING)

    try:
        result = run_jaxjob(
            job,
            artifacts_dir=artifacts_dir,
            on_metrics=(tracking.log_metrics_cb() if tracking else None),
        )
        if tracking:
            tracking.log_outputs(
                steps=result.steps,
                throughput=result.throughput,
                throughput_unit=f"{result.unit}/sec",
                wall_time=result.wall_time,
                param_count=result.param_count,
                **result.program_outputs(),
                # Preemption-requeue proof: a requeued attempt reports
                # where its checkpoint restore landed (None → cold
                # start), so the plane can audit that resume actually
                # resumed instead of silently burning the budget from
                # step 0 (SURVEY §5.4).
                restored_from_step=result.restored_from_step,
                **({"restore_skipped_steps": result.restore_skipped_steps}
                   if result.restore_skipped_steps else {}),
                **{f"final_{k}": v for k, v in result.final_metrics.items()},
            )
            tracking.log_succeeded()
        return 0
    except Exception as exc:
        traceback.print_exc()
        if tracking:
            tracking.log_failed(reason=type(exc).__name__, message=str(exc)[:2000])
        return 1
    finally:
        if tracking:
            tracking.close()


if __name__ == "__main__":
    sys.exit(main())
