"""Communication-efficiency audit (VERDICT r5 next-round #2/#4).

Static accounting of what the compiler actually emits for each
(model, mesh, schedule) point:

- ``hlo``     parses compiled HLO for collectives (all-reduce,
  all-gather, reduce-scatter, all-to-all, collective-permute) and
  estimates bytes moved per op from shapes + replica groups — the
  GSPMD-style "communication is explicit in the sharded program"
  property, turned into a report.
- ``audit``   lowers/compiles the real ``build_train_step`` program per
  schedule point on the 8-device virtual CPU mesh and summarizes its
  collectives.
- ``budgets`` per-schedule collective budgets checked in CI: an
  accidental reshard fails the build instead of silently costing 4.7x.
- ``aot``     strictly-timeouted subprocess probe of AOT topology-only
  TPU compilation: TPU HLO/cost stats with no chip attached — or a
  recorded negative result.

Run ``python -m polyaxon_tpu.perf --help`` (docs/performance.md
"Communication audit" has the playbook).
"""

from polyaxon_tpu.perf.hlo import (
    CollectiveOp,
    parse_collectives,
    summarize_collectives,
)

__all__ = ["CollectiveOp", "parse_collectives", "summarize_collectives"]
