"""Builder of the hybrid state-space decoder family: the zoo's
``CausalTransformerLM(mixer="hybrid", hybrid=HybridSpec(...))`` (Mamba-2
layers beside softmax attention layers, a kind a layer as the
published ``layer_types`` lists them, and the four published
multipliers) and its net, served from weights
in the compute dtype alone. Every leaf is drawn in float32 from the
seed and only its rounding to the compute dtype is kept, as a
deployment serves a bf16 checkpoint.

The draw is the published initialisation of Mamba-2 where the
recurrence's reach depends on it (the configuration file's ``assumed``
says why): ``A`` uniform in [1, 16], the step's bias the inverse
softplus of a log-uniform step in [1e-3, 1e-1], ``D`` = 1, the
convolution uniform by its fan-in; every matrix normal by its fan-in,
unit gains, and embedding rows of variance ``1 / (embedding_multiplier^2
hidden)``: the scaled row has unit NORM. The head is tied, so a token's
own row meets itself in its logit; at unit scale that product (hidden /
multiplier) would stand 20 deviations above every other logit, every
position would predict its own input, and no fault in any layer could
move a served token.
"""
import math

import jax
import jax.numpy as jnp

from benchmarks.models import weights

#: the published ``layer_types`` as the zoo names its layer kinds
KINDS = {"mamba": "mamba2", "attention": "softmax"}


def spec(config: dict):
    """The zoo's description of the hybrid from the published keys; a
    configuration whose keys this family does not serve is refused."""
    from deeplearning4j_tpu.ops.ssm import HybridSpec

    refused = {
        "routed experts": config["num_local_experts"]
        or config["num_experts_per_tok"],
        "rotary or learned positions":
            config["position_embedding_type"] != "nope",
        "attention or projection biases":
            config["attention_bias"] or config["mamba_proj_bias"],
        "more than one B/C group": config["mamba_n_groups"] != 1,
        "an untied head": not config["tie_word_embeddings"],
        "a norm other than RMSNorm":
            config["normalization_function"] != "rmsnorm",
        "an activation other than silu": config["hidden_act"] != "silu",
        "a feed-forward width other than shared_intermediate_size":
            config["intermediate_size"]
            != config["shared_intermediate_size"],
    }
    for what, found in refused.items():
        if found:
            raise ValueError(f"this builder does not serve {what}")
    d_inner = config["mamba_expand"] * config["hidden_size"]
    if config["mamba_n_heads"] * config["mamba_d_head"] != d_inner:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand "
                         "* hidden_size")
    return HybridSpec(
        kinds=tuple(KINDS[k] for k in config["layer_types"]),
        d_inner=d_inner, n_heads=config["mamba_n_heads"],
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"],
        norm_eps=float(config["rms_norm_eps"]))


def scalars(config: dict) -> dict:
    """The published multipliers and eps, as the zoo's arguments."""
    return {name: float(config[key]) for name, key in (
        ("embedding_multiplier", "embedding_multiplier"),
        ("residual_multiplier", "residual_multiplier"),
        ("logits_scaling", "logits_scaling"),
        ("attention_multiplier", "attention_multiplier"),
        ("norm_eps", "rms_norm_eps"))}


def init_of(config: dict):
    taps = config["mamba_d_conv"]
    bound = 1.0 / math.sqrt(taps)
    conv = ("uniform", -bound, bound) if config["mamba_conv_bias"] \
        else ("const", 0.0)

    def of(path, shape):
        leaf = path[-1]
        if leaf.endswith("gamma") or leaf == "D":
            return ("const", 1.0)
        if leaf in ("b", "bo"):         # the published model has none
            return ("const", 0.0)
        if leaf == "conv_w":
            return ("uniform", -bound, bound)
        if leaf == "conv_b":
            return conv
        if leaf == "A_log":             # A uniform in [1, 16]
            return ("log_of_uniform", 1.0, 16.0)
        if leaf == "dt_bias":           # softplus(dt_bias) log-uniform
            return ("step_bias", 1e-3, 1e-1)
        if path == ("layer_0", "W"):
            return ("normal", 1.0 / (config["embedding_multiplier"]
                                     * math.sqrt(config["hidden_size"])))
        return ("normal", weights.fan_in_std(shape))
    return of


def draw(kind, key, shape):
    """One leaf in float32."""
    name, *args = kind
    if name == "const":
        return jnp.full(shape, args[0], jnp.float32)
    if name == "normal":
        return jax.random.normal(key, shape, jnp.float32) * args[0]
    lo, hi = args
    if name == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name == "log_of_uniform":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    if name == "step_bias":
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi)))
        return step + jnp.log(-jnp.expm1(-step))    # inverse softplus
    raise ValueError(f"unknown draw {name!r}")


def make_weights(shapes, seed: int, init):
    """Every leaf of ``shapes`` from the seed, each drawn in float32
    and kept as its rounding to the leaf's dtype. One jitted program a
    KIND of top-level entry (an embedding, a Mamba layer, an attention
    layer, a norm, a head), called once an entry under that entry's
    own key: 36 Mamba layers share one small program, where one
    program over all 40 layers' leaves was the largest entry of the
    persistent compile cache (26.8 MB of its 200; my chip run 2, PR 43)
    and took 5.8 s of every warm set-up to load."""
    key = weights.seed_key(seed)
    makers, out = {}, {}
    for n, (name, entry) in enumerate(sorted(shapes.items())):
        flat, treedef = jax.tree_util.tree_flatten_with_path(entry)
        plan = tuple(
            (sds.shape, jnp.dtype(sds.dtype).name, init(
                (name,) + tuple(getattr(k, "key", str(k)) for k in path),
                sds.shape)) for path, sds in flat)
        if plan not in makers:
            makers[plan] = jax.jit(lambda k, plan=plan: [
                draw(kind, jax.random.fold_in(k, i), shape).astype(dtype)
                for i, (shape, dtype, kind) in enumerate(plan)])
        out[name] = jax.tree_util.tree_unflatten(
            treedef, makers[plan](jax.random.fold_in(key, n)))
    return out


def build(config: dict, seed: int, mark=lambda what: None) -> dict:
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    mark("program imported")
    hidden = config["hidden_size"]
    model = CausalTransformerLM(
        vocab_size=config["vocab_size"], hidden=hidden,
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        max_len=config["assumed"]["max_len"],
        ffn_mult=config["shared_intermediate_size"] / hidden,
        rope_theta=None, tie_embeddings=True,
        # never trained here: a stateless updater holds no moments
        updater=upd.Sgd(learning_rate=0.0),
        compute_dtype=config["compute_dtype"], seed=seed & 0x7FFFFFFF,
        mixer="hybrid", hybrid=spec(config), **scalars(config))
    net, shapes = weights.init_traced(model.init)
    mark("zoo's init() done")
    served = config["compute_dtype"] or "float32"
    net.params = make_weights(
        jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, served),
                     shapes), seed, init_of(config))
    return {"model": model, "net": net}
