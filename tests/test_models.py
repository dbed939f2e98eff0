"""Model zoo: output shapes, dtypes, gradient flow (SURVEY.md §4 unit tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import available_models, get_model



@pytest.mark.parametrize(
    "name,kwargs,in_shape",
    [
        ("mlp", {"hidden": (64,)}, (4, 28, 28, 1)),
        ("lenet5", {}, (4, 28, 28, 1)),
        ("resnet20", {}, (4, 28, 28, 1)),
        ("resnet50", {}, (2, 32, 32, 3)),
    ],
)
def test_forward_shapes(name, kwargs, in_shape):
    model = get_model(name, num_classes=10, **kwargs)
    x = jnp.zeros(in_shape, jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (in_shape[0], 10)
    assert logits.dtype == jnp.float32


def test_registry():
    assert set(available_models()) == {"mlp", "lenet5", "resnet20", "resnet50", "vit",
                                       "causal_lm", "nemotron_h"}
    with pytest.raises(ValueError):
        get_model("nope")


def test_lenet_dropout_needs_rng_only_in_train():
    model = get_model("lenet5", num_classes=10)
    x = jnp.ones((2, 28, 28, 1))
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    # train=True with different dropout rngs gives different outputs
    a = model.apply(variables, x, train=True, rngs={"dropout": jax.random.PRNGKey(1)})
    b = model.apply(variables, x, train=True, rngs={"dropout": jax.random.PRNGKey(2)})
    assert not np.allclose(a, b)
    # eval path is deterministic
    c = model.apply(variables, x, train=False)
    d = model.apply(variables, x, train=False)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(d))


def test_resnet_batch_stats_update():
    model = get_model("resnet20", num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 28, 28, 1))
    variables = model.init({"params": jax.random.PRNGKey(1)}, x, train=False)
    assert "batch_stats" in variables
    _, updated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    old = jax.tree.leaves(variables["batch_stats"])
    new = jax.tree.leaves(updated["batch_stats"])
    assert any(not np.allclose(o, n) for o, n in zip(old, new))


def test_gradients_finite():
    model = get_model("lenet5", num_classes=10)
    x = jax.random.uniform(jax.random.PRNGKey(0), (4, 28, 28, 1))
    y = jnp.array([0, 1, 2, 3])
    variables = model.init({"params": jax.random.PRNGKey(1)}, x, train=False)

    def loss(params):
        logits = model.apply({"params": params}, x, train=False)
        import optax

        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    grads = jax.grad(loss)(variables["params"])
    for g in jax.tree.leaves(grads):
        assert np.all(np.isfinite(g))
    # at least one nonzero gradient leaf
    assert any(np.abs(g).sum() > 0 for g in jax.tree.leaves(grads))


def test_lenet_conv1_s2d_matches_direct():
    """The polyphase space-to-depth conv1 (round 5) is the SAME function
    as the direct 5x5 C_in=1 conv, from the SAME parameter layout —
    checkpoints interchange between the two forms."""
    import jax
    import numpy as np
    from distributed_tensorflow_ibm_mnist_tpu.models import get_model

    direct = get_model("lenet5", num_classes=10, dtype=jnp.float32,
                       dropout_rate=0.0)
    poly = get_model("lenet5", num_classes=10, dtype=jnp.float32,
                     dropout_rate=0.0, conv1_s2d=True)
    x = jnp.asarray(
        np.random.default_rng(0).random((4, 28, 28, 1)), jnp.float32)
    params = direct.init(jax.random.PRNGKey(0), x, train=False)["params"]
    # identical param trees: the polyphase form declares conv1/kernel+bias
    p2 = poly.init(jax.random.PRNGKey(0), x, train=False)["params"]
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(p2)
    assert params["conv1"]["kernel"].shape == p2["conv1"]["kernel"].shape

    a = direct.apply({"params": params}, x, train=False)
    b = poly.apply({"params": params}, x, train=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
