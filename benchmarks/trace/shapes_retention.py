"""Operations and bytes a power-retention decoder needs, computed from
its shapes: the companion of ``shapes.py`` for the configurations
whose blocks keep a recurrent state.

The state is counted by its LOGICAL size, the symmetric second power
of a ``d``-wide key (``d (d + 1) / 2`` products: 8,256 for 128),
whatever the program's stored layout pads it to: a roofline share is a
share of what the algorithm needs.
"""


def state_dim(config: dict) -> int:
    """``D = d (d + 1) / 2``: rows of a kv head's state."""
    d = config["head_dim"]
    return d * (d + 1) // 2


def layer_params(config: dict) -> int:
    """Matrix parameters of one block: q and o (F x F each), k and v
    (F x Hkv d each), the SwiGLU's three. The gate's F x Hkv, its bias
    and the norm gains are left out (0.01% of a layer)."""
    f = config["hidden_size"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return 2 * f * f + 2 * f * kv + 3 * f * config["intermediate_size"]


def embedding_and_head_params(config: dict) -> int:
    """The embedding and the untied output head."""
    return 2 * config["vocab_size"] * config["hidden_size"]


def weight_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """Every matrix the gateway holds, in the compute dtype alone."""
    return bytes_per_weight * (
        config["num_hidden_layers"] * layer_params(config)
        + embedding_and_head_params(config))


def decode_weight_bytes(config: dict, bytes_per_weight: int = 2) -> float:
    """Weight bytes one decode step must read: every layer's matrices
    and the output head, once, however many slots are live (the
    embedding contributes one row a slot: left out)."""
    return float(bytes_per_weight * (
        config["num_hidden_layers"] * layer_params(config)
        + config["vocab_size"] * config["hidden_size"]))


def state_bytes_per_layer(config: dict) -> int:
    """One sequence's state ``S`` in one layer: ``Hkv x D x d``
    float32 (the issue's 33.8 MB)."""
    return (4 * config["num_key_value_heads"] * state_dim(config)
            * config["head_dim"])


def normaliser_bytes_per_layer(config: dict) -> int:
    """One sequence's normaliser ``z`` in one layer: ``Hkv x D``."""
    return 4 * config["num_key_value_heads"] * state_dim(config)


def state_pool_bytes(config: dict, slots: int) -> int:
    """The states ``S`` of ``slots`` sequences, all layers."""
    return (slots * config["num_hidden_layers"]
            * state_bytes_per_layer(config))


def decode_state_bytes_per_slot(config: dict) -> float:
    """State bytes one live slot's decode step must move: ``S`` and
    ``z`` of every layer, read once and written once. What the
    program counts as ``state_bytes`` on its decode-step records."""
    return float(2 * config["num_hidden_layers"] * (
        state_bytes_per_layer(config)
        + normaliser_bytes_per_layer(config)))
