"""Device time of the primary experts' operations over the device's
busy time in the trace, for a configuration that counts its experts
under ``moe_num_primary_experts`` and their width under
``moe_ffn_hidden_size``.

`experts_routed.busy_share_pct`'s own rule (`expert_ops`: an operand or
the result is a stack of expert weights ``bf16[.., E, D, F]`` /
``[.., E, F, D]`` with E the experts of a layer, or ``L·E`` where the
stack is handed whole to the grouped matmul), read with this
configuration's keys: every expert is held here, so E is the published
count. The router, the dispatch on either side and the attention are
left out. A configuration without those keys, or a trace in which
nothing matches, gives nothing to read."""
from harness import spec, trace_reduce


def read(ctx):
    config = ctx["config"]
    held = config.get("moe_num_primary_experts")
    if ctx["kind"] != "serve" or ctx.get("trace") is None or not held:
        return None
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    mine = spec.load_reader("experts_routed.busy_share_pct").expert_ops(
        trace_reduce.leaf_ops(plane), config["hidden_size"],
        config["moe_ffn_hidden_size"], held,
        config.get("serve", {}).get("num_hidden_layers",
                                    config["num_hidden_layers"]))
    if not mine:
        return None
    return 100.0 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
