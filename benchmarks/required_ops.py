"""Operations the forward and backward passes REQUIRE per trained token, from
a configuration's sizes: 6 per parameter that multiplies the token (the
embedding gather multiplies nothing; a sparse MLP counts the experts a token
is routed to, and the router), plus attention counted causal and per
document. Recomputed operations do not count."""

from benchmarks.costs import flash


def matmul_params_per_token(cfg: dict) -> int:
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv_heads = cfg["num_key_value_heads"]
    dim = cfg.get("head_dim") or hidden // heads
    attention = hidden * (heads + 2 * kv_heads) * dim + heads * dim * hidden
    if cfg.get("num_experts"):
        mlp = cfg["num_experts_per_tok"] * 3 * hidden * cfg["intermediate_size"]
        mlp += hidden * cfg["num_experts"]
    else:
        mlp = 3 * hidden * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attention + mlp) + hidden * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, documents: list[int]) -> float:
    heads = cfg["num_attention_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    window = cfg.get("sliding_window")
    attention = sum(
        flash.cost(k, 1, documents, heads, cfg["num_key_value_heads"], dim, 2, window)["flops"]
        for k in flash.PRODUCTS
    )
    return 6 * matmul_params_per_token(cfg) + cfg["num_hidden_layers"] * attention / sum(documents)
