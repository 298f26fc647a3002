"""Device time of the routed experts' operations over the device's busy
time in the trace, for a configuration that counts its held experts
under ``num_experts`` and whose leading layers are dense:
`experts_routed.busy_share_pct`'s rule (an operand or the result is a
stack of the routed experts' weights ``bf16[.., E, D, F]`` /
``[.., E, F, D]``, E the experts held here or ``L·E`` for the stack
handed whole to the grouped matmul) with L the *expert* layers, the
depth less ``first_k_dense_replace``: 8 or 7 x 8 at the cell's cut,
where that reader would look for 8 x 8. The shared expert's
``[6144, 2048]`` has no expert dimension and is not counted. A
configuration without these keys, or a trace in which nothing matches,
gives nothing to read."""
from harness import spec, trace_reduce


def read(ctx):
    config = ctx["config"]
    held = config.get("num_experts")
    if (ctx["kind"] != "serve" or ctx.get("trace") is None or not held
            or "first_k_dense_replace" not in config
            or "moe_intermediate_size" not in config):
        return None
    layers = config.get("serve", {}).get("num_hidden_layers",
                                         config["num_hidden_layers"])
    rule = spec.load_reader("experts_routed.busy_share_pct")
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    mine = rule.expert_ops(
        trace_reduce.leaf_ops(plane), config["hidden_size"],
        config["moe_intermediate_size"], held,
        layers - config["first_k_dense_replace"])
    if not mine:
        return None
    return 100.0 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
