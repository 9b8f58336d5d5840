"""Model serving with dynamic batching — many concurrent clients, one
device: requests are queued, concatenated up to a batch limit, run as
one jitted forward, and scattered back to their callers (reference:
ParallelInference BATCHED mode + BatchedInferenceObservable,
SURVEY §3.3).

    python examples/model_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import threading
    import time

    import numpy as np
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.parallel import ParallelInference

    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(upd.Adam(learning_rate=1e-3)).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    net = MultiLayerNetwork(conf).init()

    server = ParallelInference(net, mode=ParallelInference.BATCHED,
                               batch_limit=32)
    rng = np.random.default_rng(0)
    n_clients = 8 if FAST else 32
    per_client = 4 if FAST else 16
    latencies = []
    lock = threading.Lock()

    def client(cid):
        for _ in range(per_client):
            x = rng.standard_normal((1, 16)).astype(np.float32)
            t0 = time.perf_counter()
            out = server.output(x)
            dt = time.perf_counter() - t0
            assert out.shape == (1, 4)
            assert abs(float(out.sum()) - 1.0) < 1e-4
            with lock:
                latencies.append(dt)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    n = n_clients * per_client
    lat = sorted(latencies)
    print(f"served {n} single-example requests from {n_clients} "
          f"concurrent clients in {wall:.2f}s "
          f"({n / wall:.0f} req/s through dynamic batching)")
    print(f"latency p50 {lat[len(lat) // 2] * 1e3:.1f} ms, "
          f"p95 {lat[int(len(lat) * 0.95)] * 1e3:.1f} ms")
    server.shutdown()


if __name__ == "__main__":
    main()
