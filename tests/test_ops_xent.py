"""Pallas fused softmax-xent vs the optax reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_ibm_mnist_tpu.ops.xent import softmax_xent, softmax_xent_mean


pytestmark = pytest.mark.quick  # core numerics: part of the -m quick signal loop


def _rand(n, c, seed=0, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = jax.random.normal(k1, (n, c), dtype) * 3.0
    labels = jax.random.randint(k2, (n,), 0, c)
    return logits, labels


@pytest.mark.parametrize("n,c", [(32, 10), (37, 10), (8, 128), (100, 257)])
def test_forward_matches_optax(n, c):
    logits, labels = _rand(n, c)
    got = softmax_xent(logits, labels)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    assert got.shape == (n,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,c", [(32, 10), (37, 10), (24, 200)])
def test_grad_matches_optax(n, c):
    logits, labels = _rand(n, c, seed=1)

    def mean_fused(lg):
        return softmax_xent(lg, labels).mean()

    def mean_ref(lg):
        return optax.softmax_cross_entropy_with_integer_labels(lg, labels).mean()

    g_got = jax.grad(mean_fused)(logits)
    g_want = jax.grad(mean_ref)(logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want), rtol=1e-5, atol=1e-6)


def test_jit_and_value_and_grad():
    logits, labels = _rand(64, 10, seed=2)
    loss, grad = jax.jit(jax.value_and_grad(softmax_xent_mean))(logits, labels)
    ref = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
    # grad rows sum to ~0 (softmax minus one-hot, scaled by 1/N)
    np.testing.assert_allclose(np.asarray(grad).sum(-1), 0.0, atol=1e-6)


def test_bfloat16_logits():
    logits, labels = _rand(16, 10, seed=3, dtype=jnp.bfloat16)
    got = softmax_xent(logits, labels)
    want = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)
    grad = jax.grad(lambda lg: softmax_xent(lg, labels).mean())(logits)
    assert grad.dtype == jnp.bfloat16


def test_extreme_logits_stable():
    logits = jnp.array([[1e4, -1e4, 0.0, 5.0]] * 8, jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    loss = softmax_xent(logits, labels)
    assert np.all(np.isfinite(np.asarray(loss)))
    np.testing.assert_allclose(np.asarray(loss), 0.0, atol=1e-5)


def test_train_step_with_fused_xent_matches_reference_loss():
    """End-to-end: make_train_step(fused_xent=True) == the optax loss path."""
    import optax as _optax

    from distributed_tensorflow_ibm_mnist_tpu.core.state import TrainState
    from distributed_tensorflow_ibm_mnist_tpu.core.steps import make_train_step
    from distributed_tensorflow_ibm_mnist_tpu.models import get_model

    model = get_model("mlp", num_classes=10)
    tx = _optax.sgd(0.1)
    state = TrainState.create(model, tx, jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1), jnp.uint8))
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.integers(0, 255, (32, 28, 28, 1), dtype=np.uint8)),
        "label": jnp.asarray(rng.integers(0, 10, (32,)).astype(np.int32)),
    }
    s_fused, m_fused = jax.jit(make_train_step(model, tx, fused_xent=True))(state, batch)
    s_ref, m_ref = jax.jit(make_train_step(model, tx))(state, batch)
    np.testing.assert_allclose(float(m_fused["loss"]), float(m_ref["loss"]), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        s_fused.params, s_ref.params,
    )


def test_row_tile_is_sized_to_vmem_and_oversize_rows_are_refused():
    """The tile choice that Mosaic refused on the chip (256 rows x an LM
    vocabulary = a 32 MiB block) is sized down; a class row too wide for
    even 8 rows is refused with its size, not sent to another path."""
    from distributed_tensorflow_ibm_mnist_tpu.ops import xent

    assert xent._row_tile(1024, 128) == 256            # small classes: unchanged
    assert xent._row_tile(2048, 32768) == 8            # 8 x 32768 x 4 B = 1 MiB
    assert xent._call_params(8, 32768, interpret=False) == {"interpret": False}
    wide = xent._call_params(8, 131072, interpret=False)["compiler_params"]
    assert wide.vmem_limit_bytes == 10 * 8 * 131072 * 4
    with pytest.raises(ValueError, match="MiB of VMEM"):
        xent._call_params(8, 1 << 20, interpret=False)
    assert xent._call_params(8, 1 << 20, interpret=True) == {"interpret": True}


def test_kernel_off_tpu_without_explicit_interpret_is_an_error():
    """Interpret mode is the caller's choice (ops/interpret.py), never a
    guess from the backend: with the process-wide switch off, a kernel on
    the CPU backend raises naming the backend; interpret=True still runs."""
    from distributed_tensorflow_ibm_mnist_tpu.ops import interpret

    logits, labels = _rand(8, 10)
    interpret.set_interpret(False)
    try:
        with pytest.raises(RuntimeError, match="'cpu' backend"):
            softmax_xent(logits, labels)
        got = softmax_xent(logits, labels, interpret=True)
    finally:
        interpret.set_interpret(True)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
