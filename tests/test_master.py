"""Multi-node TrainingMaster tests on the virtual 8-device CPU mesh.

Reference analogs: TestSparkMultiLayerParameterAveraging and
GradientSharingTrainingTest run Spark ``local[*]`` — multi-node
simulated in one JVM (SURVEY §4). Here the 8 virtual devices play the
workers and the masters drive the same ParallelWrapper modes a real
multi-host mesh would.
"""
import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.parallel import (
    ParameterAveragingTrainingMaster, SharedTrainingMaster,
    ShardedDataSetIterator, SparkDl4jMultiLayer,
)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _net(seed=42):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(upd.Adam(learning_rate=0.05))
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int64)
    yh = np.eye(2, dtype=np.float32)[y]
    return [DataSet(x[i:i + 64], yh[i:i + 64]) for i in range(0, n, 64)]


def test_parameter_averaging_master_learns():
    master = (ParameterAveragingTrainingMaster.Builder(64)
              .averaging_frequency(2)
              .collect_training_stats()
              .build())
    trainer = SparkDl4jMultiLayer(_net(), master)
    net = trainer.fit(ListDataSetIterator(_data()), epochs=6)
    assert trainer.score() < 0.35
    assert trainer.stats, "collect_training_stats recorded nothing"
    x = np.asarray(_data(64)[0].features)
    out = np.asarray(net.output(x))
    assert out.shape == (64, 2)


def test_shared_training_master_learns():
    master = (SharedTrainingMaster.Builder(64)
              .threshold(1e-3)
              .build())
    trainer = SparkDl4jMultiLayer(_net(), master)
    trainer.fit(ListDataSetIterator(_data()), epochs=6)
    assert trainer.score() < 0.35


def test_spark_computation_graph():
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel import SparkComputationGraph
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(upd.Adam(learning_rate=0.05))
            .graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_out=16, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(**{"in": InputType.feed_forward(4)})
            .build())
    g = ComputationGraph(conf).init()
    master = ParameterAveragingTrainingMaster.Builder(64).build()
    trainer = SparkComputationGraph(g, master)
    trainer.fit(ListDataSetIterator(_data()), epochs=6)
    assert trainer.score() < 0.35


def test_masters_config_roundtrip():
    m = (SharedTrainingMaster.Builder(32)
         .threshold(5e-4).residual_post_processor_clip(3.0).build())
    d = m.to_json()
    assert d["@class"] == "SharedTrainingMaster"
    assert d["threshold"] == 5e-4 and d["residual_clip"] == 3.0
    m2 = (ParameterAveragingTrainingMaster.Builder(32)
          .averaging_frequency(7).build())
    assert m2.to_json()["averaging_frequency"] == 7


def test_sharded_iterator_partitions():
    data = _data(256)
    shards = [list(ShardedDataSetIterator(data, i, 4)) for i in range(4)]
    # every batch lands in exactly one shard
    assert sum(len(s) for s in shards) == len(data)
    seen = {id(ds) for s in shards for ds in s}
    assert len(seen) == len(data)
    # reset() propagates to resettable bases
    it = ShardedDataSetIterator(ListDataSetIterator(data), 0, 2)
    n1 = len(list(it))
    it.reset()
    assert len(list(it)) == n1
