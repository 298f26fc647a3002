"""How unevenly the window's decode steps loaded the experts: in each
expert layer the busiest expert's routed (row, choice) pairs over the
mean expert's, averaged over the expert layers; 1.0 is perfect balance.
From the engine's `moe_expert_tokens` counter (`/v1/stats`: [expert
layer][expert], live rows' pairs added inside the decode program) read
at the window's two edges. A program without the counter gives nothing
to read."""


def read(ctx):
    if ctx["kind"] != "serve" or "open" not in ctx["stats"]:
        return None          # the edges are read in traced runs only
    before = ctx["stats"]["open"].get("moe_expert_tokens")
    after = ctx["stats"]["close"].get("moe_expert_tokens")
    if not before or not after:
        return None
    ratios = []
    for row_a, row_b in zip(before, after):
        routed = [b - a for a, b in zip(row_a, row_b)]
        if sum(routed) <= 0:
            return None
        ratios.append(max(routed) * len(routed) / sum(routed))
    return sum(ratios) / len(ratios)
