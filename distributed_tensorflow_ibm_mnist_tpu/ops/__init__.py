"""Custom TPU ops: Pallas kernels for the hot paths.

The reference consumed its kernels (cuDNN conv, Eigen softmax/xent) through
the tensorflow-gpu wheel (SURVEY.md §2.2); XLA:TPU emits ours, and the ops in
this package are the hand-written Pallas exceptions for cases where fusion
control matters.  The CPU test suite runs every op through the Pallas
interpreter — an explicit choice (ops/interpret.py), never a fallback — so
it exercises identical code paths (SURVEY.md §4).
"""

from distributed_tensorflow_ibm_mnist_tpu.ops.xent import (  # noqa: F401
    softmax_xent,
    softmax_xent_mean,
)
