"""The latent-attention decoder with sigmoid-routed experts
(``polyaxon_tpu/models/kimi_k2.py``), from the keys of Kimi-K2.6's
published ``config.json`` (``model_type: kimi_k2``, the DeepSeek-V3
block): MLA in every layer (``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``) under the
``rope_scaling`` YaRN rule, the first ``first_k_dense_replace`` layers'
FFN dense (``intermediate_size``), the others' ``n_routed_experts``
experts of ``moe_intermediate_size`` scored by sigmoid, chosen by score
plus a bias (``noaux_tc``), ``num_experts_per_tok`` a token, beside
``n_shared_experts`` shared; an untied head.

**The cut.** Depth keeps the published first layers in their order: the
dense layer and the expert layers behind it. The other two cuts are the
chip's share of a stated deployment (``deployment``: so many chips share
each layer, this is rank ``rank`` of them): ``n_routed_experts`` counts
the routed experts held here, the contiguous block of that rank, while
the router keeps its published width
(``reduced.n_routed_experts.source``); ``vocab_size`` counts the rows of
the table and of the head held here (``deployment.vocab_shards`` ways).
`check` holds the configuration's keys, its ``reduced`` and its
``deployment`` against each other.
"""

from __future__ import annotations


def held(config: dict) -> tuple:
    """(first, count, routed): the routed experts held here among those
    the router scores."""
    count = config["n_routed_experts"]
    cut = config.get("reduced", {}).get("n_routed_experts")
    if not cut:
        return 0, count, count
    return config["deployment"]["rank"] * count, count, cut["source"]


def check(config: dict) -> None:
    """What the program's decoder cannot express, and what a cut of
    this configuration may not change."""
    if config.get("scoring_func") != "sigmoid" or config.get(
            "topk_method") != "noaux_tc":
        raise ValueError("the program's router scores by sigmoid and "
                         "chooses by score plus bias (`noaux_tc`)")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("the program's router chooses among all experts "
                         "(`n_group` = `topk_group` = 1)")
    if config.get("n_shared_experts") != 1:
        raise ValueError("the program's expert block has one shared expert")
    if config.get("moe_layer_freq", 1) != 1:
        raise ValueError("the program's decoder has an expert block in "
                         "every layer past the dense ones (`moe_layer_freq`)")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLPs are SwiGLU (silu)")
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise ValueError("the program's decoder has no attention bias and "
                         "an untied head")
    if config.get("num_nextn_predict_layers"):
        raise ValueError("the program's decoder has no next-n prediction "
                         "layers")
    if not config.get("norm_topk_prob", True):
        raise ValueError("the program's router renormalises the chosen "
                         "weights (`norm_topk_prob`)")
    rule = config.get("rope_scaling") or {}
    if rule.get("type") != "yarn" or rule.get("mscale") != rule.get(
            "mscale_all_dim"):
        raise ValueError("the program's rotary rule is yarn with mscale == "
                         "mscale_all_dim")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has a key and a value a head")
    cut = config.get("reduced", {})
    for key, entry in cut.items():
        if entry["serve"] != config[key]:
            raise ValueError(f"`reduced.{key}` says {entry['serve']}, the "
                             f"configuration {config[key]}")
    if config["num_hidden_layers"] <= config["first_k_dense_replace"]:
        raise ValueError("a cut keeps the dense layers and an expert layer")
    _, count, routed = held(config)
    deployment = config.get("deployment", {})
    shared_by = deployment.get("chips_sharing_a_layer", 1)
    if count * shared_by != routed:
        raise ValueError(f"{shared_by} chips of {count} experts do not hold "
                         f"the router's {routed}")
    if "vocab_size" in cut and (
            config["vocab_size"] * deployment.get("vocab_shards", shared_by)
            != cut["vocab_size"]["source"]):
        raise ValueError("the vocabulary slice is not this deployment's")
    if ("n_routed_experts" in cut or "vocab_size" in cut) and shared_by < 2:
        raise ValueError("a share of the experts or of the vocabulary "
                         "needs a deployment of several chips a layer")


def build(config: dict, role: str):
    import jax.numpy as jnp

    from polyaxon_tpu.models import kimi_k2

    check(config)
    section = config.get(role, {})
    layers = int(section.get("num_hidden_layers",
                             config["num_hidden_layers"]))
    if layers != config["num_hidden_layers"]:
        raise ValueError(f"the `{role}` section's depth {layers} is not the "
                         "depth the configuration states")
    first, count, routed = held(config)
    rule = config["rope_scaling"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return kimi_k2, kimi_k2.KimiK2Config(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=layers, n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(rule["factor"]),
        rope_original_max=int(rule["original_max_position_embeddings"]),
        rope_beta_fast=float(rule["beta_fast"]),
        rope_beta_slow=float(rule["beta_slow"]),
        rope_mscale=float(rule["mscale"]),
        rope_mscale_all_dim=float(rule["mscale_all_dim"]),
        ffn_dim=config["intermediate_size"],
        first_dense=config["first_k_dense_replace"],
        n_experts=routed, experts_per_token=config["num_experts_per_tok"],
        moe_ffn_dim=config["moe_intermediate_size"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        held_experts=(first, count),
        norm_eps=float(config["rms_norm_eps"]), dtype=dtype,
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))


def parameters(config: dict) -> dict:
    """Parameters by part, from the file's own keys: a layer's attention
    (q_a, its norm, q_b, kv_a, its norm, kv_b, o), a dense MLP, an
    expert block beside its routed experts (router with its bias, the
    shared expert), one routed expert, a layer's two norms, a vocabulary
    table."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, r = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    fm = config["moe_intermediate_size"]
    _, _, routed = held(config)
    return {
        "attn": (d * rq + rq + rq * h * (dn + dr) + d * (r + dr) + r
                 + r * h * (dn + dv) + h * dv * d),
        "dense": 3 * d * config["intermediate_size"],
        "beside": d * routed + routed + config["n_shared_experts"] * 3 * d * fm,
        "expert": 3 * d * fm,
        "norms": 2 * d,
        "table": d * config["vocab_size"],
    }


def parameters_here(config: dict, layers: int, active: bool = False) -> int:
    """Parameters of `layers` layers as cut with the table, the head
    and the final norm; ``active``: with the experts a token's pass
    reads, wherever they are held."""
    n = parameters(config)
    dense = min(config["first_k_dense_replace"], layers)
    experts = (config["num_experts_per_tok"] if active else held(config)[1])
    return (layers * (n["attn"] + n["norms"]) + dense * n["dense"]
            + (layers - dense) * (n["beside"] + experts * n["expert"])
            + 2 * n["table"] + config["hidden_size"])


def forward_flops_per_token(config: dict, layers: int, seq_len: int) -> float:
    """Matmul flops of the forward pass a token at the depth as cut and
    with the share of the experts held here, attention in the absorbed
    form a decode step runs: 64 heads of (576 + 512) a position."""
    n = parameters(config)
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    _, count, routed = held(config)
    pairs = config["num_experts_per_tok"] * count / routed
    dense = min(config["first_k_dense_replace"], layers)
    attend = 2 * h * (2 * r + config["qk_rope_head_dim"]) * seq_len
    return float(layers * (2 * n["attn"] + attend) + dense * 2 * n["dense"]
                 + (layers - dense) * 2 * (n["beside"] + pairs * n["expert"])
                 + 2 * n["table"])
