"""Device time of the routed experts' operations over the device's busy
time in the trace.

The routed experts are found by what the trace prints of each operation
(the instruction's whole text, which names the type of the result and
of every operand), by shape from the configuration's keys: with experts
of width F (`moe_intermediate_size`) on a width D (`moe_latent_size`
where the experts work in a latent space, else `hidden_size`), an
operation belongs to them if one of its operands or its result is a
stack of expert weights, ``bf16[.., D, F]`` or ``bf16[.., F, D]`` behind
at least one leading dimension (the layers' stack ``[L, E, D, F]``, a
layer's slice ``[E, D, F]``, the stack handed whole to the grouped
matmul as ``[L·E, D, F]``): the grouped matmuls of a prefill, the
batched matmuls of a decode step with whatever the compiler fused
behind them (the activation, the weighted combine), and any copy or
cast of the weights on their way to a kernel.

Types, not names: an instruction's name means another operation in
every program of the trace. The router, the shared expert and the
latent projections are plain matmuls on other shapes and are left out,
as is the dispatch on either side. Written against a kept trace of
`nemotron3_super_serve_batchgen` (tests/fixtures/nemotron_h_ops.json
holds its names). A configuration without routed experts, or a trace in which
nothing matches, gives nothing to read."""
import re

from harness import trace_reduce


def expert_ops(events: list, inner: int, width: int) -> list:
    """The events of `events` (leaf operations) that take or make a
    stack of expert weights."""
    d, f = inner, width
    stack = re.compile(rf"\bbf16\[(\d+,)+({d},{f}|{f},{d})\]")
    return [ev for ev in events if stack.search(ev["name"])]


def read(ctx):
    config = ctx["config"]
    experts = config.get("n_routed_experts") or config.get("num_experts")
    if ctx["kind"] != "serve" or ctx.get("trace") is None or not experts:
        return None
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    mine = expert_ops(
        trace_reduce.leaf_ops(plane),
        config.get("moe_latent_size") or config["hidden_size"],
        config["moe_intermediate_size"])
    if not mine:
        return None
    return 100.0 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
