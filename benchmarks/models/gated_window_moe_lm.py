"""Builder of the gated windowed mixture-of-experts decoder family: the
zoo's ``CausalTransformerLM(window=..., window_layers=...,
heads_by_layer=..., rope_by_kind=..., attn_gate=True,
experts=ExpertSpec(score="softmax_topk", n_shared=1, first_dense=1,
scale=...))`` (full layers beside sliding-window layers that differ in
their head count and in their rotary rule, a kind, a head count and a
feed-forward a layer as the published ``layer_types``,
``num_attention_heads_per_layer`` and ``mlp_layer_types`` list them,
each read for itself; a sigmoid gate a head in front of ``W_o``; tiny
SwiGLU experts beside a shared one after the leading dense layers) and
its net, served from weights in the compute dtype alone. Every leaf is
drawn in float32 from the seed and only its rounding to the compute
dtype is kept, as a deployment serves a bf16 checkpoint; the router
stays float32.
"""
import jax
import jax.numpy as jnp

from benchmarks.models import weights
# unit gains, no biases, every matrix normal by its fan-in (an expert's
# own; the gate's ``W_og`` too, so that a gate's logit has unit
# variance and the gates spread around one half), unit embedding rows
from benchmarks.models.window_moe_lm import init_of

#: the published names of the two kinds of softmax layer
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def rope_rule(published: dict, head_dim: int):
    """One kind's entry of ``rope_parameters`` as the zoo's rule."""
    from deeplearning4j_tpu.ops.rotary import RopeRule

    kind = published["rope_type"]
    if kind not in ("default", "yarn"):
        raise ValueError(f"this builder does not serve rope_type {kind!r}")
    return RopeRule(
        theta=float(published["rope_theta"]),
        rotary_dim=int(round(published["partial_rotary_factor"]
                             * head_dim)),
        yarn=None if kind == "default" else (
            float(published["factor"]),
            float(published["original_max_position_embeddings"]),
            float(published["beta_fast"]), float(published["beta_slow"])),
        factor=float(published.get("attention_factor", 1.0)))


def specs(config: dict):
    """The zoo's arguments from the published keys and the ``assumed``
    fields that settle what they leave open; a configuration whose
    keys this family does not serve is refused."""
    from deeplearning4j_tpu.ops.moe import ExpertSpec

    n = config["num_hidden_layers"]
    assumed = config["assumed"]
    sparse = [k == "sparse" for k in config["mlp_layer_types"][:n]]
    first_dense = sparse.index(True) if True in sparse else n
    refused = {
        "an attention bias": config["attention_bias"],
        "a tied head": config["tie_word_embeddings"],
        "router weights on the experts' input":
            config["moe_apply_router_weight_on_input"],
        f"gating {assumed['gating']!r}":
            config["gating"] and assumed["gating"] != "per-head",
        f"rotary pairing {assumed['rotary_pairing']!r}":
            assumed["rotary_pairing"] != "half-split",
        "a window that leaves the query's own position out":
            not assumed["window_counts_own"],
        "lists that do not name every layer": any(
            len(config[k]) < n for k in (
                "layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer")),
        "dense layers after the first sparse one":
            not all(sparse[first_dense:]),
        "a shared expert of another width":
            config["shared_expert_intermediate_size"]
            % config["moe_intermediate_size"],
    }
    for what, found in refused.items():
        if found:
            raise ValueError(f"this builder does not serve {what}")
    experts = ExpertSpec(
        width=config["moe_intermediate_size"],
        n_held=config["num_experts"], n_routed=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        scale=float(config["moe_routed_scaling_factor"]),
        n_shared=config["shared_expert_intermediate_size"]
        // config["moe_intermediate_size"],
        first_dense=first_dense, score=assumed["routing"],
        unit="swiglu")
    kinds = [KINDS[k] for k in config["layer_types"][:n]]
    hd = config["head_dim"]
    return experts, dict(
        window=config["sliding_window"],
        window_layers=tuple(i for i, k in enumerate(kinds)
                            if k == "window"),
        heads_by_layer=tuple(config["num_attention_heads_per_layer"][:n]),
        rope_by_kind={KINDS[k]: rope_rule(v, hd)
                      for k, v in config["rope_parameters"].items()
                      if k in KINDS},
        attn_gate=bool(config["gating"]))


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
        ffn_mult=config["intermediate_size"] / config["hidden_size"],
        max_len=config["assumed"]["max_len"],
        rope_theta=None, tie_embeddings=False,
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
