"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up rebuild of the capabilities of Eclipse Deeplearning4j
(reference: OrenBochman/deeplearning4j) designed for TPU hardware:
JAX/XLA for compute, whole-step ``jax.jit`` tracing instead of eager
per-op JNI dispatch, ``jax.sharding`` meshes instead of
ParallelWrapper/Aeron, Pallas kernels for ops XLA lacks.

Layer map (vs. the reference; see SURVEY.md):

=====================  ==============================================
Reference              This package
=====================  ==============================================
libnd4j kernels        XLA (via jax.numpy/lax) + ``ops/`` Pallas kernels
INDArray / Nd4j        ``ndarray.NDArray`` façade over ``jax.Array``
SameDiff               ``autodiff.samediff.SameDiff`` tracing frontend
MultiLayerNetwork      ``nn.multilayer.MultiLayerNetwork``
ComputationGraph       ``nn.graph.ComputationGraph``
Updaters               ``nn.updaters`` (optax-backed)
ParallelWrapper        ``parallel.wrapper.ParallelWrapper`` (mesh DP)
Aeron param server     XLA collectives over ICI/DCN (``parallel``)
DataVec                ``data.records`` / ``data.transform``
Evaluation             ``eval_`` package
ModelSerializer        ``serialization``
=====================  ==============================================
"""

__version__ = "0.1.0"

from deeplearning4j_tpu import dtypes as dtypes
from deeplearning4j_tpu.ndarray import NDArray, Nd4j
from deeplearning4j_tpu import environment as environment

# tier-2 runtime flags (env vars — reference ND4JEnvironmentVars)
if environment.get_flag("DL4J_TPU_DEFAULT_DTYPE") != "float32":
    dtypes.set_default_dtype(
        environment.get_flag("DL4J_TPU_DEFAULT_DTYPE"))
environment.apply_startup_flags()

# persistent XLA compile cache (perf/compile_cache.py): configured at
# import so every jit in this process — and every sibling worker
# process — reads/writes the shared on-disk cache (DL4J_TPU_COMPILE_CACHE)
from deeplearning4j_tpu.perf import compile_cache as _compile_cache
from deeplearning4j_tpu.perf import sentry as _sentry

_compile_cache.configure_from_env()
# every compile's phases (trace, lower, compile or load) join the
# always-on record ring, the eager ones too (obs/trace.py)
_sentry.install_compile_listener()

__all__ = ["NDArray", "Nd4j", "dtypes", "environment", "__version__"]
