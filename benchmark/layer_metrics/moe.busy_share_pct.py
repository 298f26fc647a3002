"""Device time of the expert block's operations over the device's busy
time in the trace.

The expert block is found by what the trace prints of each operation
(the instruction's whole text): with E experts of width F on hidden D
(the configuration's `num_experts`, `moe_intermediate_size`,
`hidden_size`),

- the casts of the stacked expert weights: a `convert` whose result is
  `bf16[.., E, D, F]` or `bf16[.., E, F, D]`;
- every operation that takes such a cast as an operand (the three
  expert matmuls, with whatever the compiler fused behind them: the
  activation, the weighted combine).

The one-hot dispatch into the per-expert buffers `[E, C, D]` is left
out: at 32 slots and 32 experts its shape is the convolution state's
too, and it is under 1% of the block. Written against a kept trace of `lfm2_8b_a1b_serve_batchgen`
(tests/fixtures/moe_ops.json holds its names). A configuration without
routed experts, or a trace in which nothing matches, gives nothing to
read."""
import re

from harness import trace_reduce


def expert_ops(events, experts: int, hidden: int, width: int):
    """The events of `events` (leaf operations, parsed) that belong to
    the expert block."""
    e, d, f = experts, hidden, width
    stack = re.compile(rf"^bf16\[(\d+,)?{e},({d},{f}|{f},{d})\]$")
    casts = {ev["op"] for ev in events
             if ev["opcode"] == "convert" and stack.match(ev["shape"])}
    if not casts:
        return []
    uses = re.compile(r"%(" + "|".join(re.escape(c) for c in sorted(casts))
                      + r")[,)]")
    return [ev for ev in events
            if ev["op"] in casts
            or uses.search(ev["name"].partition(" = ")[2])]


def read(ctx):
    config = ctx["config"]
    if (ctx["kind"] != "serve" or ctx.get("trace") is None
            or not config.get("num_experts")):
        return None
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    mine = expert_ops(trace_reduce.leaf_ops(plane), config["num_experts"],
                      config["hidden_size"], config["moe_intermediate_size"])
    if not mine:
        return None
    return 100.0 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
