"""Builder of the retention decoder family: the zoo's
``CausalTransformerLM(mixer="power_retention")`` and its net, served
from weights in the compute dtype alone. Every leaf is drawn in
float32 from the seed and only its rounding to the compute dtype is
kept, as a deployment serves a bf16 checkpoint: the gateway makes no
second copy of weights that already have the dtype it computes in.
"""
import jax

from benchmarks.models import weights


def _init_of(gate_bias: float):
    """Unit norm gains, no biases, the gate's bias the program's own
    constant, every matrix normal by its fan-in."""
    def init_of(path, shape):
        leaf = path[-1]
        if leaf in ("gamma", "q_gamma", "k_gamma"):
            return ("const", 1.0)
        if leaf == "bgate":
            return ("const", gate_bias)
        if leaf in ("b", "bo"):     # the published model has no biases
            return ("const", 0.0)
        if path == ("layer_0", "W"):
            return ("normal", 1.0)  # embedding rows; the norm rescales
        return ("normal", weights.fan_in_std(shape))
    return init_of


def build(config: dict, seed: int, mark=lambda what: None) -> dict:
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.ops import retention
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    mark("program imported")
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"]
    assumed = config["assumed"]
    if config["head_dim"] != hidden // heads:
        raise ValueError("the zoo's block takes head_dim = hidden/heads")
    if config.get("sliding_window") is not None or config.get(
            "use_sliding_window"):
        raise ValueError("the zoo's decoder has no sliding window")
    if config.get("tie_word_embeddings") or config.get("attention_bias"):
        raise ValueError("this builder serves untied heads, no biases")
    if assumed["power"] != 2:
        raise ValueError("the program's retention has the power 2")
    if assumed["gate_bias"] != retention.GATE_BIAS_INIT:
        raise ValueError("the gate's bias is the program's constant, "
                         f"{retention.GATE_BIAS_INIT}")
    model = CausalTransformerLM(
        vocab_size=config["vocab_size"], hidden=hidden,
        n_layers=config["num_hidden_layers"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        max_len=assumed["max_len"],
        ffn_mult=config["intermediate_size"] / hidden,
        rope_theta=float(config["rope_theta"]), tie_embeddings=False,
        # never trained here: a stateless updater holds no moments
        updater=upd.Sgd(learning_rate=0.0),
        compute_dtype=config["compute_dtype"], seed=seed & 0x7FFFFFFF,
        mixer="power_retention")
    net, shapes = weights.init_traced(model.init)
    mark("zoo's init() done")
    served = config["compute_dtype"] or "float32"
    net.params = weights.weight_maker(
        jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, served),
                     shapes),
        seed, _init_of(retention.GATE_BIAS_INIT))()
    return {"model": model, "net": net}
