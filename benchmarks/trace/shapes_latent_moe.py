"""Parameters, bytes and operations of a latent-attention,
mixture-of-experts decoder as ONE CHIP of an expert-parallel group
holds it, computed from the configuration: the companion of
``shapes.py`` for the configurations whose cache is a latent and whose
feed-forward routes.

Counted by what the work NEEDS: the experts a step hits, the rows that
are live; never by what an implementation happens to touch.
"""


def attention_params(config: dict) -> int:
    """One block's latent attention: ``q_a`` (F x q_rank), ``q_b``
    (q_rank x H (nope + rope)), ``kv_a`` (F x (kv_rank + rope)),
    ``kv_b`` (kv_rank x H (nope + v)), ``o`` (H v x F). The two
    latents' norm gains are left out."""
    f, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    return (f * q_rank + q_rank * h * (nope + rope)
            + f * (kv_rank + rope) + kv_rank * h * (nope + v)
            + h * v * f)


def dense_ffn_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_params(config: dict) -> int:
    """One routed expert (a shared expert has the same width)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_params(config: dict) -> int:
    """The router scores ALL the published experts."""
    return (config["hidden_size"]
            * config["published"]["n_routed_experts"])


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def dense_layer_params(config: dict) -> int:
    return attention_params(config) + dense_ffn_params(config)


def expert_layer_params(config: dict) -> int:
    """An expert layer as this chip holds it: attention, the shared
    experts, the router and the ``n_routed_experts`` held here."""
    return (attention_params(config) + router_params(config)
            + (config["n_shared_experts"] + config["n_routed_experts"])
            * expert_params(config))


def embedding_and_head_params(config: dict) -> int:
    """The embedding and the untied head over the vocabulary's slice."""
    return 2 * config["vocab_size"] * config["hidden_size"]


def weight_params(config: dict) -> int:
    return (config["first_k_dense_replace"] * dense_layer_params(config)
            + expert_layers(config) * expert_layer_params(config)
            + embedding_and_head_params(config))


def weight_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """Every matrix the gateway holds: the compute dtype, but the
    router in float32."""
    return (bytes_per_weight * weight_params(config)
            + (4 - bytes_per_weight) * expert_layers(config)
            * router_params(config))


def decode_fixed_weight_bytes(config: dict,
                              bytes_per_weight: int = 2) -> float:
    """Weight bytes EVERY decode step must read, whatever it routes:
    all layers' attention, the dense layers' feed-forward, the shared
    experts, the routers (float32) and the output head, once each (the
    embedding contributes one row a slot: left out)."""
    moe = expert_layers(config)
    return float(bytes_per_weight * (
        config["num_hidden_layers"] * attention_params(config)
        + config["first_k_dense_replace"] * dense_ffn_params(config)
        + moe * config["n_shared_experts"] * expert_params(config)
        + config["vocab_size"] * config["hidden_size"])
        + 4 * moe * router_params(config))


def expert_bytes(config: dict, bytes_per_weight: int = 2) -> float:
    """One routed expert's matrices: what a step reads for each held
    expert that at least one of its tokens chose."""
    return float(bytes_per_weight * expert_params(config))


def latent_row_values(config: dict) -> int:
    """``[c_kv | k_rope]``: a cached position's values in one layer."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def latent_row_bytes(config: dict, bytes_per_value: int = 2) -> float:
    """A cached position's bytes over all layers: what one of a decode
    step's ``latent_rows`` costs its attention to read."""
    return float(bytes_per_value * config["num_hidden_layers"]
                 * latent_row_values(config))


def latent_row_flops(config: dict) -> float:
    """Operations of the absorbed attention for one cached position,
    all layers: every head's score against the row (``kv_rank + rope``
    multiply-adds) and its weighted sum of the latent (``kv_rank``)."""
    return float(2 * config["num_hidden_layers"]
                 * config["num_attention_heads"]
                 * (latent_row_values(config) + config["kv_lora_rank"]))


def latent_pool_bytes(config: dict, slots: int, positions: int,
                      bytes_per_value: int = 2) -> float:
    """The latent rows of ``slots`` sequences of ``positions`` each."""
    return slots * positions * latent_row_bytes(config, bytes_per_value)
