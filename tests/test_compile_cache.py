"""The persistent compile cache is placed from OUTSIDE the program.

``utils/compile_cache.py`` is the one place that writes jax's cache
directory option: never when ``JAX_COMPILATION_CACHE_DIR`` is exported (jax
adopted it at import), else the fixed ``<checkout>/.cache/xla`` — never a
temp dir, because the path is how the next process finds the entries.
"""

import json
import os
import pathlib
import re
import subprocess
import sys

import jax

from distributed_tensorflow_ibm_mnist_tpu.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent

# builds a Trainer and an InferenceEngine (tiny, CPU) and reports what the
# process's cache configuration ended up as, and every warning raised
PROBE = r"""
import json, warnings
import jax, jax.numpy as jnp
from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import InferenceEngine
from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import compile_cache_dir
from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

with warnings.catch_warnings(record=True) as caught:
    warnings.simplefilter("always")
    Trainer(RunConfig(model="mlp", model_kwargs={"hidden": (8,)}, synthetic=True,
                      n_train=64, n_test=32, batch_size=32, epochs=1, quiet=True))
    model = get_model("causal_lm", num_classes=16, dim=16, depth=1, heads=2,
                      dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    InferenceEngine(model, params, slots=1, max_len=16)
print(json.dumps({
    "config_dir": jax.config.jax_compilation_cache_dir,
    "resolved": compile_cache_dir(),
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "warnings": [str(w.message) for w in caught],
}))
"""


def test_exported_dir_is_left_alone_by_trainer_and_engine(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR exported, building a Trainer and an
    InferenceEngine leaves jax's option equal to it (the program sets no
    directory of its own) and warns about no redirect.  A subprocess: jax
    reads the variable at import."""
    want = str(tmp_path / "xla")
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{compile_cache.ENV_VAR: want})
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=str(REPO),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["config_dir"] == want and rec["resolved"] == want
    assert rec["min_secs"] == 0.1  # the one knob the program does lower
    assert not [w for w in rec["warnings"] if "redirect" in w.lower()]


def test_unset_resolves_to_the_fixed_checkout_dir(monkeypatch):
    """Unset, the directory is <checkout>/.cache/xla — identical across two
    calls and in a second process; on the CPU backend the cache then stays
    OFF unless a harness opts in (no directory is configured)."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(REPO / ".cache" / "xla")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.compile_cache_dir() == want
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache "
         "import compile_cache_dir\n"
         "print(compile_cache_dir()); print('jax' in sys.modules)"],
        env=env, cwd=str(REPO), capture_output=True, text=True, timeout=60)
    # same path in a second process, resolved without importing jax (so a
    # parent that must stay off the chip can inspect the cache)
    assert out.stdout.split() == [want, "False"], out.stderr[-1000:]
    # the CPU default is a no-op that configures nothing
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_one_site_sets_the_cache_dir_and_no_temp_cache_paths_remain():
    """A grep test (like tests/test_lint_tracing.py): exactly one
    ``config.update`` of jax's cache-directory option in the repo —
    utils/compile_cache.py, under the variable-unset guard — and no
    ``mkdtemp`` compile-cache path under scripts/ or examples/.  The
    directories ``.gitignore`` names are not the repo: a chip comparison
    leaves a copy of the parent tree under one of them (``.scratch/``,
    ``.cache/``)."""
    option = "jax_compilation_" + "cache_dir"  # split: this file is grepped too
    ignored = {line.strip().rstrip("/") for line in
               (REPO / ".gitignore").read_text().splitlines()
               if line.strip().endswith("/")}
    assert {".cache", ".scratch", "chiprun_out"} <= ignored
    sites = []
    for path in REPO.rglob("*.py"):
        rel = path.relative_to(REPO)
        if ignored & set(rel.parts[:-1]):
            continue
        text = path.read_text()
        if re.search(r"""config\.update\(\s*["']""" + option, text):
            sites.append(str(rel))
        if rel.parts[0] in ("scripts", "examples"):
            for m in re.finditer(r"mkdtemp\([^)]*\)", text):
                assert not re.search(r"xc|xla|compile|cache", m.group(0)), (
                    f"{rel}: temp-dir compile cache {m.group(0)!r} — a "
                    "directory that moves never hits")
            assert "DTM_COMPILE_" + "CACHE" not in text, rel  # the retired variable
    assert sites == ["distributed_tensorflow_ibm_mnist_tpu/utils/compile_cache.py"]
    src = (REPO / sites[0]).read_text()
    guard = src.index("if not os.environ.get(ENV_VAR):")
    assert guard < src.index(f'jax.config.update("{option}"')
