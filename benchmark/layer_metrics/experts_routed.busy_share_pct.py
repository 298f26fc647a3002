"""Device time of the *routed* experts' operations over the device's
busy time in the trace, for a configuration whose shared expert has the
routed experts' own shape.

`experts.busy_share_pct` finds the routed experts by a stack of expert
weights ``bf16[.., D, F]`` / ``[.., F, D]`` behind at least one leading
dimension. Where the shared expert is as wide as a routed one (here
both are 2,048 x 512), its stack over the layers ``bf16[L, D, F]`` and a
layer's slice ``bf16[1, D, F]`` match that too, and the share would
count the shared expert's matmuls with the routed ones. This reader
asks for the dimension that counts the experts as well: an operand or
the result is ``bf16[.., E, D, F]`` / ``[.., E, F, D]`` with E the
experts held here (the layers' stack ``[L, E, D, F]``, a layer's slice
``[E, D, F]``) or ``L·E`` (the stack handed whole to the grouped matmul
as ``[L·E, D, F]``), from the configuration's keys.

Types, not names, as there. The router, the shared expert and its gate
are plain matmuls on other shapes (``[L, D, F]``, ``[D, F]``) and are
left out, as is the dispatch on either side. Written against a kept
trace of `qwen3_next_serve_longgen` (tests/fixtures/qwen3_next_ops.json
holds its names). A configuration without routed experts, or a trace
in which nothing matches, gives nothing to read."""
import re

from harness import trace_reduce


def expert_ops(events: list, inner: int, width: int, held: int,
               layers: int) -> list:
    """The events of `events` (leaf operations) that take or make a
    stack of the routed experts' weights."""
    d, f = inner, width
    stack = re.compile(rf"\bbf16\[(\d+,)*({held}|{layers * held}),"
                       rf"({d},{f}|{f},{d})\]")
    return [ev for ev in events if stack.search(ev["name"])]


def read(ctx):
    config = ctx["config"]
    held = config.get("num_experts") or config.get("n_routed_experts")
    if ctx["kind"] != "serve" or ctx.get("trace") is None or not held:
        return None
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    mine = expert_ops(
        trace_reduce.leaf_ops(plane),
        config.get("moe_latent_size") or config["hidden_size"],
        config["moe_intermediate_size"], held,
        config.get("serve", {}).get("num_hidden_layers",
                                    config["num_hidden_layers"]))
    if not mine:
        return None
    return 100.0 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
