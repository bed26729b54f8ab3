"""tpu-dl4j: a TPU-native deep-learning framework with the capability surface of
Deeplearning4j 0.7.x (reference surveyed in SURVEY.md).

Architecture is idiomatic JAX/XLA — functional layers over parameter pytrees,
jit-compiled train steps, `jax.sharding.Mesh` data/tensor parallelism over ICI —
not a port of the reference's JVM design.

Top-level convenience re-exports mirror the reference's most-used entry points
(reference: deeplearning4j-nn/src/main/java/org/deeplearning4j/nn).
"""

# the package's own import is a span of the process's start
# (observability/startup.py): its clock starts here, with nothing but ``time``
# imported, so that everything below, ``observability`` included, is inside it
import time as _time

_T0_NS = _time.time_ns()

__version__ = "0.1.0"

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

__all__ = [
    "NeuralNetConfiguration",
    "MultiLayerConfiguration",
    "MultiLayerNetwork",
    "__version__",
]

from deeplearning4j_tpu.observability.startup import record_import as _record_import

_record_import(_T0_NS)
