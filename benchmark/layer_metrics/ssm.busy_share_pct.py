"""Device time of the Mamba-2 layers' recurrence (the chunked scan of a
prefill, the one-step update of a decode step) over the device's busy
time in the trace.

The recurrence is found by what the trace prints of each operation (the
instruction's whole text, which names the type of the result and of
every operand), from the configuration's own keys: with H heads of P
channels in G groups, state N and chunk Q, an operation belongs to it
if its result or one of its operands is

- *the state*: ``f32[.., H, P, N]`` or, heads by group, ``f32[.., G,
  H/G, P, N]`` (the decode step's in-place update of a layer's rows,
  whose result and first operand are the whole leaf ``f32[L, slots, H,
  P, N]``, and the read of the new state for ``y``; a prefill's chunk
  states, their carry and the row's write);
- *one of the chunked scan's own products*: ``f32[.., Q, G, H/G, P]``
  (the outputs inside and across chunks) and ``f32[.., G, H/G, Q, Q]``
  / ``f32[.., G, Q, Q]`` (the decays and ``C·Bᵀ``).

Types, not names: an instruction's name (``fusion.52``) means another
operation in every program of the trace. The projections on either side
are plain matmuls on other shapes and are left out. Written against a
kept trace of `nemotron3_super_serve_batchgen` (tests/fixtures/
nemotron_h_ops.json holds its names). A configuration without such layers, or
a trace in which nothing matches, gives nothing to read."""
import re

from harness import trace_reduce


def shapes(config: dict) -> tuple:
    """(the state's type, the chunked scan's products' types) as regular
    expressions over an instruction's text."""
    H, P, N = (config["mamba_num_heads"], config["mamba_head_dim"],
               config["ssm_state_size"])
    G, Q = config["n_groups"], config["chunk_size"]
    R = H // G
    lead = r"\bf32\[(\d+,)*"
    state = re.compile(lead + rf"({H}|{G},{R}),{P},{N}\]")
    scan = re.compile(lead + rf"({Q},{G},{R},{P}|{G},{R},{Q},{Q}|{G},{Q},{Q})\]")
    return state, scan


def recurrence_ops(events: list, config: dict) -> list:
    """The events of `events` (leaf operations) that belong to the
    recurrence."""
    state, scan = shapes(config)
    return [ev for ev in events
            if state.search(ev["name"]) or scan.search(ev["name"])]


def read(ctx):
    config = ctx["config"]
    if (ctx["kind"] != "serve" or ctx.get("trace") is None
            or not config.get("mamba_num_heads")):
        return None
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    mine = recurrence_ops(trace_reduce.leaf_ops(plane), config)
    if not mine:
        return None
    return 100.0 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
