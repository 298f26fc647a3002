"""Device idle per decode step under `engine:step.keys` (the per-slot
`fold_in` programs and their `jnp.stack`), traced window."""
from harness import phase_idle


def read(ctx):
    return phase_idle.idle_ms_per_step(ctx, "keys")
