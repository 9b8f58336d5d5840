"""Operations and bytes an algorithm needs, computed from its shapes.

Kept with the benchmark so that no PR which claims a gain can change
what a roofline share is a share of. A multiply-add counts as 2 FLOPs.
"""


def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)


def resnet_convs(config: dict):
    """Every convolution of the bottleneck ResNet as ``(name, h_out,
    w_out, kh, kw, c_in, c_out)``; the stride of a down-sampling block
    sits on its first 1x1 (and on its shortcut), as the zoo builds it."""
    size = _same_out(config["image_size"], 2)
    convs = [("stem", size, size, 7, 7, config["num_channels"], 64)]
    size = _same_out(size, 2)               # 3x3 max-pool, stride 2
    c_in = 64
    for s, blocks in enumerate(config["stage_blocks"]):
        mid = 64 * 2 ** s
        out = 4 * mid
        for i in range(blocks):
            if i == 0 and s > 0:
                size = _same_out(size, 2)
            name = f"res{s + 2}_{i}"
            convs.append((f"{name}_a", size, size, 1, 1, c_in, mid))
            convs.append((f"{name}_b", size, size, 3, 3, mid, mid))
            convs.append((f"{name}_c", size, size, 1, 1, mid, out))
            if i == 0:
                convs.append((f"{name}_sc", size, size, 1, 1, c_in, out))
            c_in = out
    return convs


def resnet_forward_conv_flops(config: dict) -> float:
    """Convolution FLOPs of one image's forward pass."""
    return float(sum(2 * h * w * kh * kw * ci * co
                     for _, h, w, kh, kw, ci, co in resnet_convs(config)))


def resnet_train_conv_flops(config: dict) -> float:
    """Convolution FLOPs one trained image requires: the forward pass,
    the gradient of every kernel, and the gradient of every
    convolution's input but the stem's (the image needs none)."""
    fwd = resnet_forward_conv_flops(config)
    _, h, w, kh, kw, ci, co = resnet_convs(config)[0]
    return 3 * fwd - 2 * h * w * kh * kw * ci * co


def lm_layer_params(config: dict) -> int:
    """Matrix parameters of one decoder layer (norm gains left out)."""
    f = config["hidden_size"]
    head_dim = config.get("head_dim") or f // config["num_attention_heads"]
    q = config["num_attention_heads"] * head_dim
    kv = config["num_key_value_heads"] * head_dim
    return f * q + 2 * f * kv + q * f + 3 * f * config["intermediate_size"]


def lm_decode_weight_bytes(config: dict, bytes_per_weight: int = 2) -> float:
    """Weight bytes one decode step must read: every layer's matrices
    and the output head, once, however many slots are live (the
    embedding contributes one row a slot: left out)."""
    return float(bytes_per_weight * (
        config["num_hidden_layers"] * lm_layer_params(config)
        + config["hidden_size"] * config["vocab_size"]))


def lm_kv_bytes_per_token(config: dict, bytes_per_value: int = 2) -> float:
    """Key and value bytes one cached position holds, all layers."""
    f = config["hidden_size"]
    head_dim = config.get("head_dim") or f // config["num_attention_heads"]
    return float(bytes_per_value * 2 * config["num_hidden_layers"]
                 * config["num_key_value_heads"] * head_dim)
