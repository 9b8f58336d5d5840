"""Builder of the windowed mixture-of-experts decoder family: the zoo's
``CausalTransformerLM(window=..., window_layers=..., rope_layers=...,
experts=ExpertSpec(score="softmax_topk", unit="reglu",
route_before_mixer=True))`` (sliding-window softmax layers with rotary
positions beside full layers without positions, a kind a layer as the
published ``sliding_window_layout`` and ``rope_layout`` list them, each
read for itself; small ReGLU experts routed by the pre-attention rows)
and its net, served from weights in the compute dtype alone. Every
leaf is drawn in float32 from the seed and only its rounding to the
compute dtype is kept, as a deployment serves a bf16 checkpoint; the
router stays float32.
"""
import math

import jax
import jax.numpy as jnp

from benchmarks.models import weights

#: a layer's stacked experts ``[n_experts, fan_in, fan_out]``
STACKED = ("Weg", "Weu", "Wed")


def init_of(path, shape):
    """Unit norm gains, no biases, every matrix normal by its fan-in
    (an expert's own, not the stack's), unit embedding rows."""
    leaf = path[-1]
    if leaf.endswith("gamma"):
        return ("const", 1.0)
    if leaf in ("b", "bo", "br"):
        return ("const", 0.0)
    if path == ("layer_0", "W"):
        return ("normal", 1.0)      # embedding rows; the norm rescales
    if leaf in STACKED:
        return ("normal", math.sqrt(1.0 / shape[-2]))
    return ("normal", weights.fan_in_std(shape))


def layers_of(layout):
    return tuple(i for i, on in enumerate(layout) if on)


def specs(config: dict):
    """The zoo's arguments from the published keys; a configuration
    whose keys this family does not serve is refused."""
    from deeplearning4j_tpu.ops.moe import ExpertSpec

    n = config["num_hidden_layers"]
    refused = {
        "a router without the softmax over the chosen":
            not (config["moe_primary_router_apply_softmax"]
                 and config["norm_topk_prob"]),
        "rotary scaling": config["rope_scaling"] is not None,
        "a tied head": config["tie_word_embeddings"],
        "layouts that do not name every layer":
            len(config["rope_layout"]) < n
            or len(config["sliding_window_layout"]) < n,
    }
    for what, found in refused.items():
        if found:
            raise ValueError(f"this builder does not serve {what}")
    experts = ExpertSpec(
        width=config["moe_ffn_hidden_size"],
        n_held=config["moe_num_primary_experts"],
        n_routed=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        n_shared=0, score="softmax_topk", unit="reglu",
        route_before_mixer=True)
    return experts, dict(
        window=config["sliding_window_size"],
        window_layers=layers_of(config["sliding_window_layout"][:n]),
        rope_layers=layers_of(config["rope_layout"][:n]))


def build(config: dict, seed: int, mark=lambda what: None) -> dict:
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.ops.moe import FLOAT32_LEAVES
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    mark("program imported")
    experts, layers = specs(config)
    model = CausalTransformerLM(
        vocab_size=config["vocab_size"],
        hidden=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_len=config["assumed"]["max_len"],
        rope_theta=float(config["rope_theta"]), tie_embeddings=False,
        norm_eps=float(config["rms_norm_eps"]),
        # never trained here: a stateless updater holds no moments
        updater=upd.Sgd(learning_rate=0.0),
        compute_dtype=config["compute_dtype"], seed=seed & 0x7FFFFFFF,
        experts=experts, **layers)
    net, shapes = weights.init_traced(model.init)
    mark("zoo's init() done")
    served = config["compute_dtype"] or "float32"
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    served_shapes = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(
            s.shape, jnp.float32 if getattr(path[-1], "key", None)
            in FLOAT32_LEAVES else served) for path, s in flat])
    net.params = weights.weight_maker(served_shapes, seed, init_of)()
    return {"model": model, "net": net}
