"""Operations and bytes a hybrid state-space decoder needs, computed
from its shapes: the companion of ``shapes.py`` for the configurations
whose layers are Mamba-2 mixers beside a few attention layers
(``layer_types``), each followed by the same gated feed-forward.

The state is counted as the algorithm holds it: ``heads x head_dim x
d_state`` float32 a sequence and Mamba layer, beside the convolution's
tail of ``d_conv - 1`` un-convolved rows in the compute dtype.
"""


def d_inner(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def conv_dim(config: dict) -> int:
    """Channels of the convolution: ``x``, ``B`` and ``C``."""
    return d_inner(config) + 2 * config["mamba_n_groups"] * config[
        "mamba_d_state"]


def layers_of(config: dict, kind: str) -> int:
    return sum(k == kind for k in config["layer_types"])


def ffn_params(config: dict) -> int:
    """The gated feed-forward: ``W_in`` F x 2I and ``W_out`` I x F."""
    return 3 * config["hidden_size"] * config["shared_intermediate_size"]


def mamba_layer_params(config: dict) -> int:
    """One Mamba layer, every leaf: in-projection, convolution with
    its bias, ``A_log``/``D``/``dt_bias``, the gated norm's gain, the
    out-projection, the block's two norms, the feed-forward."""
    f, di = config["hidden_size"], d_inner(config)
    heads = config["mamba_n_heads"]
    in_width = 2 * di + 2 * config["mamba_n_groups"] * config[
        "mamba_d_state"] + heads
    conv = conv_dim(config) * (config["mamba_d_conv"]
                               + bool(config["mamba_conv_bias"]))
    return (f * in_width + conv + 3 * heads + di + di * f + 2 * f
            + ffn_params(config))


def attention_layer_params(config: dict) -> int:
    """One attention layer: q and o (F x F), k and v (F x Hkv d), the
    block's two norms, the feed-forward."""
    f = config["hidden_size"]
    kv = (config["num_key_value_heads"] * f
          // config["num_attention_heads"])
    return 2 * f * f + 2 * f * kv + 2 * f + ffn_params(config)


def embedding_params(config: dict) -> int:
    """The embedding, which is the tied head too."""
    return config["vocab_size"] * config["hidden_size"]


def params(config: dict) -> int:
    return (layers_of(config, "mamba") * mamba_layer_params(config)
            + layers_of(config, "attention")
            * attention_layer_params(config)
            + embedding_params(config) + config["hidden_size"])


def weight_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """Every leaf the gateway holds, in the compute dtype alone."""
    return bytes_per_weight * params(config)


def decode_weight_bytes(config: dict, bytes_per_weight: int = 2) -> float:
    """Weight bytes one decode step must read: every layer's leaves and
    the tied head, which IS the embedding matrix, once, however many
    slots are live."""
    return float(weight_bytes(config, bytes_per_weight))


def state_bytes_per_layer(config: dict) -> int:
    """One sequence's state in one Mamba layer: heads x head_dim x
    d_state float32 (2,097,152 B at 64 x 64 x 128)."""
    return 4 * d_inner(config) * config["mamba_d_state"]


def tail_bytes_per_layer(config: dict, bytes_per_value: int = 2) -> int:
    """One sequence's convolution tail in one Mamba layer."""
    return (bytes_per_value * (config["mamba_d_conv"] - 1)
            * conv_dim(config))


def state_pool_bytes(config: dict, slots: int) -> int:
    """States and tails of ``slots`` sequences and the trash page's,
    all Mamba layers."""
    return (1 + slots) * layers_of(config, "mamba") * (
        state_bytes_per_layer(config) + tail_bytes_per_layer(config))


def decode_h_bytes_per_slot(config: dict) -> float:
    """State bytes the recurrence of one live slot's decode step must
    move: every Mamba layer's state, read once and written once."""
    return float(2 * layers_of(config, "mamba")
                 * state_bytes_per_layer(config))


def decode_state_bytes_per_slot(config: dict) -> float:
    """What one live slot's decode step carries over: states and tails
    of every Mamba layer, read once and written once. What the program
    counts as ``state_bytes`` on its decode-step records."""
    return float(2 * layers_of(config, "mamba") * (
        state_bytes_per_layer(config) + tail_bytes_per_layer(config)))


def kv_bytes_per_row(config: dict, bytes_per_value: int = 2) -> int:
    """K and V of one cached position, all attention layers."""
    f = config["hidden_size"]
    kv = (config["num_key_value_heads"] * f
          // config["num_attention_heads"])
    return bytes_per_value * layers_of(config, "attention") * 2 * kv


def kv_pool_bytes(config: dict, slots: int, max_context: int) -> int:
    """Pages for every slot at full context (the trash page left out)."""
    return slots * max_context * kv_bytes_per_row(config)
