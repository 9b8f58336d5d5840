"""Builder of the dense decoder-only family: the zoo's
``CausalTransformerLM`` and its net as a user gets them, with the
benchmark's own weights from the seed in place of the zoo's draw.
"""
from benchmarks.models import weights


def _init_of(path, shape):
    leaf = path[-1]
    if leaf == "gamma":
        return ("const", 1.0)
    if leaf in ("b", "bo"):     # the published model has no biases
        return ("const", 0.0)
    if path == ("layer_0", "W"):
        return ("normal", 1.0)  # embedding rows; the first norm rescales
    return ("normal", weights.fan_in_std(shape))


def build(config: dict, seed: int, mark=lambda what: None) -> dict:
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    mark("program imported")
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"]
    if config.get("head_dim", hidden // heads) != hidden // heads:
        raise ValueError("the zoo's block takes head_dim = hidden/heads")
    if config.get("sliding_window") is not None:
        raise ValueError("the zoo's decoder has no sliding window")
    if config.get("tie_word_embeddings"):
        raise ValueError("this builder serves untied heads")
    model = CausalTransformerLM(
        vocab_size=config["vocab_size"], hidden=hidden,
        n_layers=config["num_hidden_layers"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        max_len=config["assumed"]["max_len"],
        ffn_mult=config["intermediate_size"] / hidden,
        rope_theta=float(config["rope_theta"]), tie_embeddings=False,
        # never trained here: a stateless updater holds no moments
        updater=upd.Sgd(learning_rate=0.0),
        compute_dtype=config["compute_dtype"], seed=seed & 0x7FFFFFFF)
    net, shapes = weights.init_traced(model.init)
    mark("zoo's init() done")
    net.params = weights.weight_maker(shapes, seed, _init_of)()
    return {"model": model, "net": net}
