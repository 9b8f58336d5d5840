"""Builder of the ResNet family: the zoo's ``ResNet50`` graph as a user
gets it, with the benchmark's own weights from the seed in its place.
"""
from benchmarks.models import weights


def _init_by(residual_gain: float):
    """He et al. 2015b for ReLU networks (normal, variance 2 / fan_in),
    zero shifts and biases, unit gains but the configuration's
    ``residual_bn_gain`` on the last batch norm of every bottleneck."""
    def init_of(path, shape):
        layer, leaf = path[-2], path[-1]
        if leaf == "gamma":
            return ("const",
                    residual_gain if layer.endswith("_c_bn") else 1.0)
        if leaf in ("beta", "b"):
            return ("const", 0.0)
        return ("normal", weights.fan_in_std(shape, gain=2.0))
    return init_of


def build(config: dict, seed: int, mark=lambda what: None) -> dict:
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.zoo import ResNet50

    mark("program imported")
    if list(config["stage_blocks"]) != [3, 4, 6, 3]:
        raise ValueError("the zoo builds ResNet-50's [3, 4, 6, 3] only")
    train = config["training"]
    if train["updater"] != "nesterov":
        raise ValueError(f"unknown updater {train['updater']!r}")
    size = config["image_size"]
    net, shapes = weights.init_traced(ResNet50(
        num_classes=config["num_classes"], seed=seed & 0x7FFFFFFF,
        input_shape=(size, size, config["num_channels"]),
        updater=upd.Nesterovs(learning_rate=train["learning_rate"],
                              momentum=train["momentum"]),
        compute_dtype=config["compute_dtype"]).init)
    mark("zoo's init() done")
    # the maker holds names and shapes only: the reference gets the same
    # weights again from the seed once the net is freed
    remake = weights.weight_maker(
        shapes, seed, _init_by(config["residual_bn_gain"]))
    net.params = remake()
    return {"net": net, "remake": remake}
