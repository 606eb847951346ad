"""deeplearning4j_tpu — a TPU-native deep learning framework.

A from-scratch rebuild of the Deeplearning4j capability surface
(reference: paladin74/deeplearning4j) designed TPU-first on JAX/XLA/Pallas:

- ``ops``       — named op registry with runtime-selectable Pallas kernels
                  (the libnd4j "platform helper" idea, TPU-native).
                  Reference: libnd4j/include/ops/declarable/**.
- ``autodiff``  — SameDiff-style define-then-run graph layer.
                  Reference: nd4j-api :: org.nd4j.autodiff.samediff.SameDiff.
- ``nn``        — declarative layer configs + MultiLayerNetwork /
                  ComputationGraph. Reference: deeplearning4j-nn ::
                  org.deeplearning4j.nn.{conf,multilayer,graph}.
- ``optimize``  — updaters, LR schedules, listeners, early stopping.
                  Reference: org.nd4j.linalg.learning, org.deeplearning4j.optimize.
- ``datasets``  — DataSet/DataSetIterator contracts + fetchers.
                  Reference: org.nd4j.linalg.dataset, deeplearning4j-data.
- ``datavec``   — RecordReader / TransformProcess ETL. Reference: datavec/.
- ``parallel``  — device-mesh parallelism (DP/TP/PP/SP) as XLA collectives;
                  replaces ParallelWrapper / Spark / Aeron. Reference:
                  org.deeplearning4j.parallelism.ParallelWrapper.
- ``zoo``       — model zoo. Reference: deeplearning4j-zoo.
- ``eval``      — Evaluation / ROC / RegressionEvaluation.
                  Reference: org.nd4j.evaluation.
- ``modelimport`` — Keras h5 / TF frozen-graph import.
                  Reference: deeplearning4j-modelimport, org.nd4j.imports.

Unlike the reference's per-op JNI dispatch into CUDA kernels, everything here
funnels into XLA: model configs trace to a single jitted (and, on a mesh,
pjit-sharded) XLA program per train/inference step.
"""

__version__ = "0.1.0"

from deeplearning4j_tpu.common.dtypes import DtypePolicy, get_policy, set_policy

__all__ = [
    "DtypePolicy",
    "get_policy",
    "set_policy",
    "__version__",
]
