"""Builder of the latent-attention, mixture-of-experts decoder family:
the zoo's ``CausalTransformerLM(mixer="latent", latent=..., experts=...)``
and its net, as ONE CHIP OF AN EXPERT-PARALLEL GROUP holds it (the
configuration's ``n_routed_experts`` are the experts held here,
``published.n_routed_experts`` the router's width), served from
weights in the compute dtype alone. Every leaf is drawn in float32 from
the seed and only its rounding to the compute dtype is kept, as a
deployment serves a bf16 checkpoint; the router and its correction
bias stay float32, as published.
"""
import math

import jax
import jax.numpy as jnp

from benchmarks.models import weights

#: a layer's stacked experts ``[n_held, fan_in, fan_out]``
STACKED = ("Weg", "Weu", "Wed")


def init_of(path, shape):
    """Unit norm gains, no biases, a zero correction bias, every
    matrix normal by its fan-in (an expert's own, not the stack's)."""
    leaf = path[-1]
    if leaf.endswith("gamma"):
        return ("const", 1.0)
    if leaf in ("b", "br"):
        return ("const", 0.0)
    if path == ("layer_0", "W"):
        return ("normal", 1.0)      # embedding rows; the norm rescales
    if leaf in STACKED:
        return ("normal", math.sqrt(1.0 / shape[-2]))
    return ("normal", weights.fan_in_std(shape))


def specs(config: dict):
    """The zoo's two descriptions from the published keys."""
    from deeplearning4j_tpu.ops.latent import LatentSpec
    from deeplearning4j_tpu.ops.moe import ExpertSpec

    yarn = config["rope_scaling"]
    if yarn["type"] != "yarn":
        raise ValueError("this builder knows YaRN rotary scaling only")
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]:
        raise ValueError("the program's router scores by a sigmoid and "
                         "normalises the chosen weights")
    if config["moe_layer_freq"] != 1:
        raise ValueError("every layer after the dense ones routes")
    latent = LatentSpec(
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v=config["v_head_dim"],
        yarn=(float(yarn["factor"]),
              int(yarn["original_max_position_embeddings"]),
              float(yarn["beta_fast"]), float(yarn["beta_slow"]),
              float(yarn["mscale"]), float(yarn["mscale_all_dim"])))
    experts = ExpertSpec(
        width=config["moe_intermediate_size"],
        n_held=config["n_routed_experts"],
        n_routed=config["published"]["n_routed_experts"],
        top_k=config["num_experts_per_tok"], n_group=config["n_group"],
        topk_group=config["topk_group"],
        scale=float(config["routed_scaling_factor"]),
        n_shared=config["n_shared_experts"],
        offset=int(config.get("expert_offset", 0)),
        first_dense=config["first_k_dense_replace"])
    return latent, experts


def build(config: dict, seed: int, mark=lambda what: None) -> dict:
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.ops.moe import FLOAT32_LEAVES
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    mark("program imported")
    if config.get("tie_word_embeddings") or config.get("attention_bias"):
        raise ValueError("this builder serves untied heads, no biases")
    hidden = config["hidden_size"]
    latent, experts = specs(config)
    model = CausalTransformerLM(
        vocab_size=config["vocab_size"], hidden=hidden,
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        max_len=config["assumed"]["max_len"],
        ffn_mult=config["intermediate_size"] / hidden,
        rope_theta=float(config["rope_theta"]), tie_embeddings=False,
        # never trained here: a stateless updater holds no moments
        updater=upd.Sgd(learning_rate=0.0),
        compute_dtype=config["compute_dtype"], seed=seed & 0x7FFFFFFF,
        mixer="latent", latent=latent, experts=experts)
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
