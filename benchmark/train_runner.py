"""The training cells: the configuration through ``Trainer``, whole epochs
of its scanned ``run_epoch`` program inside the window.

Set-up: build the ``Trainer`` (its data and weights come from ``--seed``),
check the loss of one batch against the plain reference, run one epoch to
compile and warm.  The window then runs whole epochs until ``--seconds``
have passed and reads back each epoch's losses; the rate is every token of
those epochs over all the time they took.
"""

from __future__ import annotations

import math
import time

from benchmark import harness, reference

# bf16 forward against the f32 reference on ~16k-32k tokens of a
# random-init model: PR 23 read 2.3e-4 on the v5e.  2e-3 is some ten times
# that and a hundred times under what a wrong mask, position or window does
# to the loss of a model whose loss is ln(vocab) = 10.8
LOSS_TOL = 2e-3


def model_kwargs(cfg: dict) -> dict:
    kw = {"dim": cfg["hidden_size"], "depth": cfg["num_hidden_layers"],
          "heads": cfg["num_attention_heads"],
          "heads_kv": cfg["num_key_value_heads"],
          "mlp_ratio": cfg["intermediate_size"] // cfg["hidden_size"],
          "tie_embeddings": bool(cfg["tie_word_embeddings"])}
    kw.update(cfg.get("model_kwargs", {}))
    return kw


def run(cell: harness.Cell, devs, setup: harness.Setup) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.core.steps import make_loss_fn
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    if cell.rehearse:
        from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import set_interpret

        set_interpret(True)
    cfg, tr = cell.config, cell.traffic
    dp = int(tr.get("dp", 1))
    if dp != len(devs):
        raise SystemExit(f"traffic {cell.traffic_name} is dp={dp}, the cell has "
                         f"{len(devs)} chips")
    per_chip, steps, seq = tr["sequences_per_chip"], tr["steps_per_epoch"], tr["seq_len"]
    batch = per_chip * dp
    tracker = harness.compile_tracker()
    c0 = tracker.snapshot()
    setup.mark("import")

    mk = model_kwargs(cfg)
    if cell.rehearse:
        mk["dtype"] = jnp.float32
    trainer = Trainer(RunConfig(
        name=cell.name, model="causal_lm", model_kwargs=mk,
        dataset="retrieval",
        dataset_kwargs={"vocab": cfg["vocab_size"], "seq_len": seq},
        n_train=steps * batch, n_test=batch, batch_size=batch,
        eval_batch_size=batch, epochs=1, quiet=True, dp=dp,
        seed=cell.jax_seed(), **cfg.get("run_config", {})))
    setup.mark("weights")

    # ---- correct: one batch's loss, program against plain reference ----
    n_check = min(batch, int(tr.get("check_sequences", per_chip)))
    tokens = jax.device_put(np.asarray(trainer.train_images[:n_check]), devs[0])
    labels = jax.device_put(np.asarray(trainer.train_labels[:n_check]), devs[0])
    # on one chip, whatever the mesh: a Mosaic kernel inside a jit over
    # several devices needs a shard_map, and the check is about arithmetic
    params0 = jax.device_put(trainer.state.params, devs[0])
    loss_fn = jax.jit(lambda p, b: make_loss_fn(trainer.model)(p, {}, b, None, False)[0])
    loss_program = float(loss_fn(params0, {"image": tokens, "label": labels}))
    loss_reference = reference.mean_xent(
        params0, tokens, labels,
        **reference.shape_of(cfg, window=mk.get("window", 0)))
    del params0
    loss_err = abs(loss_program - loss_reference)
    setup.mark("check")

    # ---- warm: one epoch compiles and runs the program the window uses ----
    rng = jax.random.PRNGKey(cell.jax_seed())
    state = trainer.state
    state, m = trainer._run_epoch(state, trainer.train_images,
                                  trainer.train_labels, jax.random.fold_in(rng, 0))
    losses = [np.asarray(jax.device_get(m["loss"]))]
    setup.mark("prewarm")
    c1 = tracker.snapshot()

    # ---- the window ----
    profiler = harness.ProfilerWindow(cell)
    epochs, tokens_per_epoch = 0, steps * batch * seq
    t0 = time.perf_counter()
    while True:
        first = epochs == 0
        if first:
            profiler.start()  # one epoch is traced: it is the steady state
        with harness.annotate("train_dispatch"):
            state, m = trainer._run_epoch(
                state, trainer.train_images, trainer.train_labels,
                jax.random.fold_in(rng, epochs + 1))
        with harness.annotate("readback"):
            losses.append(np.asarray(jax.device_get(m["loss"])))
        if first:
            profiler.stop()
        epochs += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= cell.seconds:
            break
    c2 = tracker.snapshot()
    trainer.state = state

    all_losses = np.concatenate(losses)
    finite = bool(np.isfinite(all_losses).all())
    correct = finite and loss_err <= LOSS_TOL and math.isfinite(loss_program)
    total_tokens = epochs * tokens_per_epoch
    window = harness.compile_delta(c2, c1)
    built = harness.compile_delta(c1, c0)
    return {
        "correct": correct, "attempted": epochs * steps,
        "failed": 0 if finite else int((~np.isfinite(all_losses)).sum()),
        "end_to_end": {
            "train_tok_per_s_per_chip": total_tokens / elapsed / dp,
            "setup_s": setup.total(),
        },
        "counters": {
            "compile_s": built["compile_s"], "setup_programs": built["programs"],
            "setup_cache_hits": built["cache_hits"],
            "window_compiles": window["programs"],
            "steps_per_epoch": steps,
            "train_program": "run_epoch", "seq_len": seq,
            "sequences_per_chip": per_chip, "window": mk.get("window", 0),
        },
        "check": {"loss_program": loss_program, "loss_reference": loss_reference,
                  "loss_err": loss_err, "tolerance": LOSS_TOL,
                  "loss_first": float(all_losses[0]),
                  "loss_last": float(all_losses[-1]), "epochs": epochs,
                  "window_s": elapsed},
        "setup": {**{k: round(v, 3) for k, v in setup.items.items()},
                  "setup_s": round(setup.total(), 3), **built},
        "profiler": profiler,
    }
