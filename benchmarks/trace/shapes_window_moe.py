"""Operations and bytes a windowed mixture-of-experts decoder needs,
computed from its shapes: the companion of ``shapes.py`` for the
configurations whose softmax layers are sliding-window layers beside
full layers (``sliding_window_layout``) and whose feed-forward is a
set of small gated experts with no shared one.

A step's bytes are of what it MUST read: the experts the step hit, the
attention's matrices, the float32 routers, the head, and the cached
positions IN RANGE (a full layer every live position, a window layer
the last ``sliding_window_size`` of them), never of what a kernel did
read.
"""


def attention_width(config: dict) -> int:
    return config["num_attention_heads"] * config["head_dim"]


def kv_width(config: dict) -> int:
    return config["num_key_value_heads"] * config["head_dim"]


def attention_params(config: dict) -> int:
    """``W_q`` and ``W_o`` (F x H d), ``W_k`` and ``W_v`` (F x Hkv d)."""
    f = config["hidden_size"]
    return 2 * f * attention_width(config) + 2 * f * kv_width(config)


def router_params(config: dict) -> int:
    return config["hidden_size"] * config["moe_num_primary_experts"]


def expert_params(config: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def expert_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    return bytes_per_weight * expert_params(config)


def layer_params(config: dict) -> int:
    """Attention, router and every expert (norm gains left out)."""
    return (attention_params(config) + router_params(config)
            + config["moe_num_primary_experts"] * expert_params(config))


def embedding_and_head_params(config: dict) -> int:
    return 2 * config["vocab_size"] * config["hidden_size"]


def weight_params(config: dict) -> int:
    return (config["num_hidden_layers"] * layer_params(config)
            + embedding_and_head_params(config))


def weight_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """Every matrix in the compute dtype, the routers in float32."""
    routers = config["num_hidden_layers"] * router_params(config)
    return bytes_per_weight * (weight_params(config) - routers) \
        + 4 * routers


def layers_of(config: dict, window: bool) -> int:
    n = config["num_hidden_layers"]
    return sum(bool(x) == window
               for x in config["sliding_window_layout"][:n])


def kv_bytes_per_row(config: dict, bytes_per_value: int = 2) -> int:
    """K and V of one cached position in ONE layer."""
    return bytes_per_value * 2 * kv_width(config)


def ring_pages(config: dict, block: int) -> int:
    """Pages of a slot's ring in a window layer."""
    return -(-config["sliding_window_size"] // block) + 1


def kv_pool_bytes(config: dict, slots: int, max_context: int,
                  block: int) -> dict:
    """The two pools as the pager holds them, the trash pages left
    out: full layers every position, window layers a ring a slot."""
    row = kv_bytes_per_row(config)
    return {"full": layers_of(config, False) * slots * max_context * row,
            "window": layers_of(config, True) * slots
            * ring_pages(config, block) * block * row}


def decode_fixed_weight_bytes(config: dict,
                              bytes_per_weight: int = 2) -> int:
    """Weight bytes every decode step must read however it routes:
    each layer's attention and float32 router, and the head (the
    embedding is a gather of a row a slot)."""
    n = config["num_hidden_layers"]
    return (n * (bytes_per_weight * attention_params(config)
                 + 4 * router_params(config))
            + bytes_per_weight * config["vocab_size"]
            * config["hidden_size"])


def decode_bytes(config: dict, experts_hit: float,
                 kv_rows: float) -> float:
    """What a decode step must read: the fixed weights, the experts it
    hit (over all layers) and the cached positions in range (over all
    layers: the program's ``kv_rows_read``)."""
    return (decode_fixed_weight_bytes(config)
            + experts_hit * expert_bytes(config)
            + kv_rows * kv_bytes_per_row(config))
