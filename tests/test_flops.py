"""FLOPs/MFU accounting + the public throughput-measurement API."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.utils.flops import (
    compiled_flops,
    device_peak_tflops,
    mfu,
)


pytestmark = pytest.mark.quick  # core numerics: part of the -m quick signal loop


def test_compiled_flops_matmul():
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((256, 256))
    flops = compiled_flops(f, a, a)
    # 2 * n^3 MACs-as-flops for a square matmul
    assert flops == 2 * 256**3


def test_peak_tflops_env_override(monkeypatch):
    monkeypatch.setenv("DTM_PEAK_TFLOPS", "123.5")
    assert device_peak_tflops() == 123.5


def test_peak_tflops_unknown_cpu(monkeypatch):
    monkeypatch.delenv("DTM_PEAK_TFLOPS", raising=False)
    # CPU device_kind is not a TPU -> None, and mfu degrades to None
    assert device_peak_tflops() is None
    assert mfu(1e12) is None


def test_peak_tflops_refuses_malformed_override_and_unknown_tpu(monkeypatch):
    monkeypatch.setenv("DTM_PEAK_TFLOPS", "fast")
    with pytest.raises(ValueError, match="DTM_PEAK_TFLOPS"):
        device_peak_tflops()
    monkeypatch.delenv("DTM_PEAK_TFLOPS")

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert device_peak_tflops(Dev()) == 197.0  # the v5e's own report: exact hit
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        device_peak_tflops(Dev())


def test_mfu_fraction(monkeypatch):
    monkeypatch.setenv("DTM_PEAK_TFLOPS", "100")
    assert abs(mfu(50e12) - 0.5) < 1e-9


def test_decode_step_flops_gqa_grouped():
    """Satellite pin for the GQA MFU fix: MHA == heads_kv=heads == the
    default, and grouping strictly reduces the count by EXACTLY the two
    grouped terms — kv projection ``2*B*dim*2*(H-Hkv)*D`` plus cache
    attention ``4*B*span*(H-Hkv)*D``.  An off-by-H regression (charging
    full width anywhere) breaks the analytic delta."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import (
        decode_step_flops,
    )

    b, span, dim, h, d = 8, 4096, 512, 8, 64
    mha = decode_step_flops(b, span, dim, h, d)
    assert mha == decode_step_flops(b, span, dim, h, d, heads_kv=h)
    assert mha == decode_step_flops(b, span, dim, h, d, heads_kv=None)

    hkv = h // 4
    gqa = decode_step_flops(b, span, dim, h, d, heads_kv=hkv)
    assert gqa < mha
    delta = 2.0 * b * dim * 2 * (h - hkv) * d + 4.0 * b * span * (h - hkv) * d
    assert mha - gqa == delta

    # depth scales the per-layer part; vocab adds the logits matmul once
    assert decode_step_flops(b, span, dim, h, d, heads_kv=hkv, depth=3) == 3 * gqa
    assert (decode_step_flops(b, span, dim, h, d, heads_kv=hkv, vocab=1000)
            == gqa + 2.0 * b * dim * 1000)

    with pytest.raises(ValueError):
        decode_step_flops(b, span, dim, h, d, heads_kv=0)
    with pytest.raises(ValueError):
        decode_step_flops(b, span, dim, h, d, heads_kv=h + 1)


def test_decode_step_flops_cp_exact_delta():
    """ISSUE 20 satellite pin: cp shrinks ONLY the cache-attention term,
    to the per-chip ceil(span/cp) width — the exact cp=1 delta is
    ``depth * 4*B*Hkv*D * (ceil(span/cp) - span)``, projections and MLP
    untouched (they replicate over the cp axis)."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import (
        decode_step_flops,
    )

    b, dim, h, d, depth = 8, 512, 8, 64, 3
    hkv = h // 4
    for span in (4096, 4097):  # even split and the ceil remainder
        for cp in (1, 2, 4):
            full = decode_step_flops(b, span, dim, h, d, heads_kv=hkv,
                                     depth=depth)
            chip = decode_step_flops(b, span, dim, h, d, heads_kv=hkv,
                                     depth=depth, cp=cp)
            want = depth * 4.0 * b * hkv * d * (-(-span // cp) - span)
            assert chip - full == want, (span, cp)
    assert decode_step_flops(b, 4096, dim, h, d, cp=1) == decode_step_flops(
        b, 4096, dim, h, d)
    with pytest.raises(ValueError):
        decode_step_flops(b, 4096, dim, h, d, cp=0)


def test_attention_flops_cp_per_chip_average():
    """Prefill's cp figure is the plain per-chip average total/cp (the
    causal ring's step imbalance sums away), composing with every other
    knob; cp=1 is the identity and cp<1 refuses."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import (
        attention_flops,
    )

    base = attention_flops(2, 128, 8, 64, causal=True, depth=3)
    for cp in (2, 4):
        assert attention_flops(2, 128, 8, 64, causal=True, depth=3,
                               cp=cp) == base / cp
    assert attention_flops(2, 128, 8, 64, cp=1) == attention_flops(
        2, 128, 8, 64)
    with pytest.raises(ValueError):
        attention_flops(2, 128, 8, 64, cp=0)


def test_ring_hop_bytes():
    """One hop = the rotating K+V blocks at the GROUPED width: exactly
    ``2 * B * S_local * H_kv * D * dtype_bytes * depth`` — an H (not
    H_kv) regression would overcharge GQA rings by the group factor."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import (
        ring_hop_bytes,
    )

    assert ring_hop_bytes(24, 2, 16) == 2 * 1 * 24 * 2 * 16 * 4 * 1
    assert ring_hop_bytes(24, 2, 16, batch=3, dtype_bytes=2,
                          depth=4) == 2 * 3 * 24 * 2 * 16 * 2 * 4
    assert ring_hop_bytes(0, 2, 16) == 0  # degenerate local slice
    for bad in (dict(seq_local=-1, heads_kv=2, head_dim=16),
                dict(seq_local=8, heads_kv=0, head_dim=16),
                dict(seq_local=8, heads_kv=2, head_dim=0)):
        with pytest.raises(ValueError):
            ring_hop_bytes(**bad)


def test_measure_throughput_public_api(monkeypatch):
    """Supported benchmark path: sane numbers, MFU populated when a peak is
    known, and the trainer's state restored untouched."""
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    monkeypatch.setenv("DTM_PEAK_TFLOPS", "100")
    t = Trainer(RunConfig(
        model="mlp", model_kwargs={"hidden": (32,)}, dataset="mnist",
        synthetic=True, n_train=256, n_test=64, batch_size=64, epochs=1,
        quiet=True, eval_batch_size=64,
    ))
    before = jax.device_get(t.state.params)
    out = t.measure_throughput(epochs=2)
    after = jax.device_get(t.state.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert out["images_per_sec"] > 0
    assert out["images_per_sec_per_chip"] == out["images_per_sec"]  # 1 chip
    assert out["epochs"] == 2 and out["chips"] == 1
    assert np.isfinite(out["last_loss"])
    assert out["model_tflops_per_sec_per_chip"] > 0
    assert 0 < out["mfu"] < 1
    # "untouched" includes the state's commitment: the fit() that follows
    # (measure, then train) must reuse the epoch program, not recompile it
    summary = t.fit()
    assert not [s for s in summary["compile_by_site"] if s.startswith("train_epoch")]


def test_measure_throughput_no_full_state_host_gather(eight_devices):
    """The pre-measurement state backup stays on device (VERDICT.md r2 item
    6): only small metric arrays may cross the host link during
    measure_throughput.  Run under dp=8/fsdp so the snapshot must also
    preserve a sharded layout."""
    from distributed_tensorflow_ibm_mnist_tpu.core import trainer as trainer_mod
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    t = Trainer(RunConfig(
        model="mlp", model_kwargs={"hidden": (64,)}, dataset="mnist",
        synthetic=True, n_train=256, n_test=64, batch_size=64, epochs=1,
        dp=8, fsdp=True, quiet=True, eval_batch_size=64,
    ))
    before = jax.device_get(t.state.params)
    spec_before = t.state.params["dense_0"]["kernel"].sharding.spec
    real_jax = trainer_mod.jax

    class _Guard:
        """jax proxy: device_get allowed for small arrays (metric readbacks)
        only — a TrainState pytree or big leaf means a full-state gather."""

        def __getattr__(self, name):
            if name == "device_get":
                return self._guarded
            return getattr(real_jax, name)

        @staticmethod
        def _guarded(x):
            if hasattr(x, "size") and getattr(x, "size", 1 << 30) <= 10_000:
                return real_jax.device_get(x)
            raise AssertionError(
                f"full-state host gather in measure_throughput: {type(x)}"
            )

    trainer_mod.jax = _Guard()
    try:
        out = t.measure_throughput(epochs=2)
    finally:
        trainer_mod.jax = real_jax
    assert out["images_per_sec"] > 0
    # state restored bit-exact, in the same sharded layout, without a gather
    assert t.state.params["dense_0"]["kernel"].sharding.spec == spec_before
    after = jax.device_get(t.state.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_summary_reports_mfu(monkeypatch):
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    monkeypatch.setenv("DTM_PEAK_TFLOPS", "100")
    t = Trainer(RunConfig(
        model="mlp", model_kwargs={"hidden": (32,)}, dataset="mnist",
        synthetic=True, n_train=256, n_test=64, batch_size=64, epochs=2,
        quiet=True, eval_batch_size=64,
    ))
    s = t.fit()
    assert s["model_tflops_per_sec_per_chip"] > 0
    assert s["mfu"] is not None


def test_cost_analysis_counts_scan_body_once():
    """Pins the XLA behavior _epoch_flops corrects for: a while-loop body's
    FLOPs are reported ONCE regardless of trip count. If a jax/XLA upgrade
    starts scaling by trip count, this fails and the steps_per_epoch
    multiplier in Trainer._epoch_flops must be removed."""
    from jax import lax

    a = jnp.ones((128, 128))
    one = jax.jit(lambda a: a @ a)
    scan4 = jax.jit(lambda a: lax.scan(lambda c, _: (c @ a, None), a, None, length=4)[0])
    # scan4 adds a couple of loop-counter flops; the matmul body must appear
    # exactly once (4x would be ~12.6M)
    assert abs(compiled_flops(scan4, a) - compiled_flops(one, a)) < 1000


def test_attention_flops_matches_dense_cost_analysis():
    """The analytic attention count (the flash-run MFU supplement,
    VERDICT.md r2 item 2) agrees with XLA's own cost analysis of the DENSE
    attention path: fwd+bwd of vanilla attention is dominated by the 4
    score/value matmuls fwd + 8 bwd = 3x fwd, which is exactly
    attention_flops(with_backward=True).  Tolerance covers the softmax
    elementwise ops cost analysis adds on top."""
    from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
        vanilla_attention,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import attention_flops

    b, s, h, d = 2, 256, 4, 64
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        for _ in range(3)
    )

    def loss(q, k, v):
        return jnp.sum(vanilla_attention(q, k, v) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    measured = compiled_flops(grad, q, k, v)
    analytic = attention_flops(b, s, h, d, with_backward=True)
    assert analytic < measured < 1.4 * analytic, (measured, analytic)
    # and the causal/fwd-only knobs scale as documented
    assert attention_flops(b, s, h, d, causal=True) == analytic / 2
    assert attention_flops(b, s, h, d, with_backward=False) == analytic / 3


def test_flash_supplement_gated_to_tpu():
    """On CPU (interpret mode) the supplement must be 0 — the interpreted
    kernel's FLOPs land in cost analysis already; adding the analytic count
    would double-book.  The meta is still captured so the TPU path works."""
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    t = Trainer(RunConfig(
        model="causal_lm",
        model_kwargs={"dim": 64, "depth": 1, "heads": 4, "attn": "flash",
                      "dtype": jnp.float32},
        dataset="retrieval", dataset_kwargs={"vocab": 16, "seq_len": 32},
        n_train=128, n_test=32, batch_size=32, epochs=1, quiet=True,
        eval_batch_size=32,
    ))
    assert t._attn_flops_meta == {"seq": 32, "heads": 4, "head_dim": 16,
                                  "depth": 1, "window": 0}
    assert t.causal is True  # family default folds into the supplement
    assert t._flash_attn_flops_per_epoch() == 0.0  # cpu backend
    # the number the TPU path would add: causal-halved, 3x-fwd, per-device
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import attention_flops

    expect = attention_flops(32, 32, 4, 16, causal=True) * t.steps_per_epoch
    assert expect > 0


def test_epoch_flops_matches_analytic():
    """Trainer._epoch_flops lands within sane bounds of the analytic matmul
    count (fwd 2*MACs; train ~3x fwd), i.e. the scan-trip scaling is applied
    exactly once."""
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    t = Trainer(RunConfig(
        model="mlp", model_kwargs={"hidden": (256,), "dtype": jnp.float32},
        dataset="mnist", synthetic=True, n_train=1024, n_test=64,
        batch_size=128, epochs=1, quiet=True, eval_batch_size=64,
    ))
    got = t._epoch_flops()
    macs_per_img = 784 * 256 + 256 * 10
    fwd_flops_epoch = 2 * macs_per_img * 128 * t.steps_per_epoch
    # train step = fwd + bwd (~2x fwd) + optimizer noise: expect ~3x fwd
    assert 2 * fwd_flops_epoch < got < 6 * fwd_flops_epoch, (got, fwd_flops_epoch)
