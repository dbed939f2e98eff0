"""Training-side compile census: per-path, per-site program counts of
``Trainer.fit()``, pinned exact.

The Trainer labels its compile sites with the parallelism PATH the run
took (``train_epoch[dp4_fsdp]``, ``eval[dp2_pp2]``, ``h2d[dp1_stream]`` —
built once at Trainer init from dp/fsdp/tp/sp/pp/sharded_update/stream)
and ``fit()``'s summary carries the by-site delta as ``compile_by_site``.
One tiny fit per path on the 8-virtual-device mesh:

* a site over its count means the path grew a program (a compile storm or
  a flapping jit cache key, even when every other test passes): it bears
  on ``setup_programs`` and ``setup_s`` of the benchmark's training cells;
* a site under its count, or missing, means the attribution regressed;
* a labelled site outside the pinned set is a new program family member.

``unattributed`` (helper jits outside any site) is not pinned: what it
counts depends on what the process compiled before.
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

_MLP = dict(
    model="mlp", model_kwargs={"hidden": (32,)}, dataset="mnist",
    synthetic=True, n_train=256, n_test=64, batch_size=64, epochs=1,
    quiet=True, eval_batch_size=64,
)
_LM_PP = dict(
    name="census_pp", model="causal_lm", dp=2, pp=2,
    model_kwargs={"dim": 32, "depth": 2, "heads": 2, "dtype": jnp.float32},
    dataset="retrieval", dataset_kwargs={"vocab": 16, "seq_len": 32},
    n_train=128, n_test=32, batch_size=32, epochs=1, quiet=True,
    eval_batch_size=32,
)

# path label -> (config, {site: programs}).  The scan epoch is ONE program
# per path and eval is one.  Stream mode compiles the chunk runner, the
# ragged-tail per-step runner and their two metric-stack helpers inside
# the epoch, and its h2d site NOTHING: device_put is a transfer, a program
# there means the input path grew a jit.
CENSUS = {
    "dp1": (_MLP, {"train_epoch[dp1]": 1, "eval[dp1]": 1}),
    "dp1_stream": (
        {**_MLP, "input_mode": "stream", "stream_chunk": 2},
        {"train_epoch[dp1_stream]": 4, "h2d[dp1_stream]": 0,
         "eval[dp1_stream]": 1}),
    "dp4": ({**_MLP, "dp": 4}, {"train_epoch[dp4]": 1, "eval[dp4]": 1}),
    "dp4_fsdp": ({**_MLP, "dp": 4, "fsdp": True},
                 {"train_epoch[dp4_fsdp]": 1, "eval[dp4_fsdp]": 1}),
    "dp4_su": ({**_MLP, "dp": 4, "sharded_update": True},
               {"train_epoch[dp4_su]": 1, "eval[dp4_su]": 1}),
    "dp2_pp2": (_LM_PP, {"train_epoch[dp2_pp2]": 1, "eval[dp2_pp2]": 1}),
}


@pytest.mark.parametrize("path", sorted(CENSUS))
def test_fit_compiles_exactly_its_paths_programs(eight_devices, path):
    cfg, pinned = CENSUS[path]
    # the counts are a cold process's: a module-level helper jit that an
    # earlier test of this worker compiled would be missing from them
    jax.clear_caches()
    trainer = Trainer(RunConfig(**cfg))
    try:
        summary = trainer.fit()
    finally:
        trainer.close()
    assert trainer._path_label == path
    by_site = {site: rec["n"]
               for site, rec in summary["compile_by_site"].items()
               if site != "unattributed"}
    assert {**{s: 0 for s in pinned}, **by_site} == pinned, by_site
