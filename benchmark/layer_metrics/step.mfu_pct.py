"""The benchmark's own flop count (harness/flops.py) times the tokens a
second a chip trained, over the chip's bf16 peak."""
from harness import flops


def read(ctx):
    if ctx["kind"] != "train" or ctx["peaks"] is None:
        return None
    per_token = flops.train_flops_per_token(
        ctx["config"], ctx["layers"], ctx["seq_len"])
    return 100.0 * per_token * ctx["tok_s_chip"] / ctx["peaks"]["bf16_flops"]
