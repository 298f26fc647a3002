"""Device time of the Gated DeltaNet layers' recurrence (the chunked
form of a prefill, the one-step update of a decode step) over the
device's busy time in the trace.

The recurrence is found by what the trace prints of each operation (the
instruction's whole text, which names the type of the result and of
every operand), from the configuration's own keys: with Hv value heads,
key size dk, value size dv and chunk C (64, the modelling code's), an
operation belongs to it if its result or one of its operands is

- *the state*: ``f32[.., Hv, dk, dv]`` (the decode step's read of a
  layer's rows out of the whole leaf ``f32[L, slots, Hv, dk, dv]``, the
  pass that takes ``Sᵀk`` and ``Sᵀq``, the in-place update; a prefill's
  carried state and the row's write);
- *one of the chunked form's own products*: ``f32[.., Hv, C, C]`` (the
  decays, ``K Kᵀ``, ``Q Kᵀ``, the triangular system; the compiler's
  solve works on it as ``[.., Hv, 1, C, C]``, its diagonal blocks) and
  ``f32[.., Hv, C, dk | dv | dk+dv]`` (the chunks of q, k, v, the solved
  ``[W | U]`` and the outputs).

Types, not names: an instruction's name means another operation in
every program of the trace. The projections on either side, the
convolution and the norms are on other shapes and are left out. Written
against a kept trace of `qwen3_next_serve_longgen` (tests/fixtures/
qwen3_next_ops.json holds its names). A configuration without such
layers, or a trace in which nothing matches, gives nothing to read."""
import re

from harness import trace_reduce

CHUNK = 64


def shapes(config: dict) -> tuple:
    """(the state's type, the chunked form's products' types) as regular
    expressions over an instruction's text."""
    H = config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    C = CHUNK
    lead = r"\bf32\[(\d+,)*"
    state = re.compile(lead + rf"{H},{dk},{dv}\]")
    widths = "|".join(str(n) for n in sorted({C, dk, dv, dk + dv}))
    chunked = re.compile(lead + rf"{H},(1,)?{C},({widths})\]")
    return state, chunked


def recurrence_ops(events: list, config: dict) -> list:
    """The events of `events` (leaf operations) that belong to the
    recurrence."""
    state, chunked = shapes(config)
    return [ev for ev in events
            if state.search(ev["name"]) or chunked.search(ev["name"])]


def read(ctx):
    config = ctx["config"]
    if (ctx["kind"] != "serve" or ctx.get("trace") is None
            or not config.get("linear_num_value_heads")):
        return None
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    mine = recurrence_ops(trace_reduce.leaf_ops(plane), config)
    if not mine:
        return None
    return 100.0 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
