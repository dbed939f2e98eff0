"""Model zoo (flax.linen modules).

Covers the reference's model layer (SURVEY.md §1 L3: the TF-tutorial
LeNet-style MNIST CNN) plus the scale-out configs from BASELINE.md
(MLP smoke model, ResNet-20, ResNet-50).

Every model follows one calling convention:
``model(x, train: bool = False)`` with optional ``dropout`` RNG and
``batch_stats`` collection, images NHWC float in [0, 1].
"""

from __future__ import annotations

from distributed_tensorflow_ibm_mnist_tpu.models.causal_lm import CausalLM
from distributed_tensorflow_ibm_mnist_tpu.models.lenet import LeNet5
from distributed_tensorflow_ibm_mnist_tpu.models.mlp import MLP
from distributed_tensorflow_ibm_mnist_tpu.models.resnet import ResNet, ResNet20, ResNet50
from distributed_tensorflow_ibm_mnist_tpu.models.transformer import VisionTransformer


def _nemotron_h(**kwargs):
    """The Nemotron-H serving model (models/nemotron_h.py), imported on
    first use: its kernels and the grouped expert product stay out of every
    other model's import."""
    from distributed_tensorflow_ibm_mnist_tpu.models.nemotron_h import NemotronHLM

    return NemotronHLM(**kwargs)


_REGISTRY = {
    "mlp": MLP,
    "lenet5": LeNet5,
    "resnet20": ResNet20,
    "resnet50": ResNet50,
    "vit": VisionTransformer,
    "causal_lm": CausalLM,
    "nemotron_h": _nemotron_h,
}


def get_model(name: str, **kwargs):
    """Build a model from the registry by name."""
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; available: {sorted(_REGISTRY)}") from None
    return cls(**kwargs)


def available_models() -> list[str]:
    return sorted(_REGISTRY)


def model_accepts(name: str, param: str) -> bool:
    """Whether a registry builder takes the given keyword (e.g. axis_name)."""
    import inspect

    try:
        builder = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; available: {sorted(_REGISTRY)}") from None
    try:
        return param in inspect.signature(builder).parameters
    except (TypeError, ValueError):
        return False


def model_default(name: str, param: str, default=None):
    """The declared default of a registry builder's keyword — e.g. the
    ``causal`` flag a model family ships with (True for causal_lm), or its
    ``heads``/``patch_size`` when the user didn't override them.  Returns
    ``default`` when the builder has no such parameter.  This is how the
    Trainer derives family semantics instead of asking the user to restate
    them (VERDICT.md r2 item 3)."""
    import inspect

    try:
        builder = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; available: {sorted(_REGISTRY)}") from None
    try:
        p = inspect.signature(builder).parameters.get(param)
    except (TypeError, ValueError):
        return default
    if p is None or p.default is inspect.Parameter.empty:
        return default
    return p.default


__all__ = ["CausalLM", "MLP", "LeNet5", "ResNet", "ResNet20", "ResNet50", "VisionTransformer", "get_model", "available_models", "model_accepts", "model_default"]
