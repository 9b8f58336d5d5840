"""Operations and bytes a gated windowed mixture-of-experts decoder
needs, computed from its shapes: the companion of ``shapes.py`` for the
configurations whose softmax layers are of two kinds that differ in
their HEAD COUNT (``layer_types`` beside
``num_attention_heads_per_layer``), hold a gate a head, and whose
feed-forward is a leading dense layer and then tiny gated experts
beside a shared one (``mlp_layer_types``). Every list is read entry
``l`` for layer ``l``, over the layers held here.

A step's bytes are of what it MUST read: the experts the step hit, the
shared expert and the dense layer, the attention's matrices and gates
by kind, the float32 routers, the head, and the cached positions IN
RANGE (a full layer every live position, a window layer the last
``sliding_window`` of them), never of what a kernel did read.
"""
from benchmarks.trace.shapes_window_moe import (  # noqa: F401
    embedding_and_head_params, kv_bytes_per_row, kv_width)


def layers(config: dict):
    return range(config["num_hidden_layers"])


def is_window(config: dict, li: int) -> bool:
    return config["layer_types"][li] == "sliding_attention"


def is_sparse(config: dict, li: int) -> bool:
    return config["mlp_layer_types"][li] == "sparse"


def layers_of(config: dict, window: bool) -> int:
    return sum(is_window(config, li) == window for li in layers(config))


def sparse_layers(config: dict) -> int:
    return sum(is_sparse(config, li) for li in layers(config))


def attention_params(config: dict, li: int) -> int:
    """``W_q`` and ``W_o`` (F x H_l d), ``W_k`` and ``W_v`` (F x Hkv d)
    and the gate ``W_og`` (F x H_l) of layer ``li``."""
    f = config["hidden_size"]
    heads = config["num_attention_heads_per_layer"][li]
    gate = f * heads if config["gating"] else 0
    return (2 * f * heads * config["head_dim"] + 2 * f * kv_width(config)
            + gate)


def router_params(config: dict) -> int:
    return config["hidden_size"] * config["num_experts"]


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    return bytes_per_weight * expert_params(config)


def shared_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config[
        "shared_expert_intermediate_size"]


def dense_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def layer_params(config: dict, li: int) -> int:
    """Attention with its gate and the layer's feed-forward: the dense
    one, or router, shared expert and every routed expert (norm gains
    left out)."""
    if not is_sparse(config, li):
        return attention_params(config, li) + dense_params(config)
    return (attention_params(config, li) + router_params(config)
            + shared_params(config)
            + config["num_experts"] * expert_params(config))


def weight_params(config: dict) -> int:
    return (sum(layer_params(config, li) for li in layers(config))
            + embedding_and_head_params(config))


def weight_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """Every matrix in the compute dtype, the routers in float32."""
    routers = sparse_layers(config) * router_params(config)
    return bytes_per_weight * (weight_params(config) - routers) \
        + 4 * routers


def ring_pages(config: dict, block: int) -> int:
    """Pages of a slot's ring in a window layer."""
    return -(-config["sliding_window"] // block) + 1


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
    each layer's attention and gate, a dense layer's feed-forward, a
    sparse layer's float32 router and shared expert, and the head (the
    embedding is a gather of a row a slot)."""
    total = bytes_per_weight * config["vocab_size"] * config["hidden_size"]
    for li in layers(config):
        total += bytes_per_weight * attention_params(config, li)
        if is_sparse(config, li):
            total += (4 * router_params(config)
                      + bytes_per_weight * shared_params(config))
        else:
            total += bytes_per_weight * dense_params(config)
    return total


def decode_bytes(config: dict, experts_hit: float,
                 kv_rows: float) -> float:
    """What a decode step must read: the fixed weights, the experts it
    hit (over all sparse layers) and the cached positions in range
    (over all layers: the program's ``kv_rows_read``)."""
    return (decode_fixed_weight_bytes(config)
            + experts_hit * expert_bytes(config)
            + kv_rows * kv_bytes_per_row(config))
