"""Per-request sampling for the serving tier (ISSUE 13).

A request carries a :class:`SamplingParams` — ``(temperature, top_p,
top_k, min_p, seed)`` — validated at submit time, and the engine turns
the per-slot values into device-side DATA planes: (slots,) float32
temperature, top-p, and min-p vectors, a (slots,) int32 top-k vector
(ISSUE 14), plus a (slots, 2) uint32 base-key plane, all fed to the
SAME compiled decode/verify programs regardless of the mix (the
one-program-many-behaviors discipline the census gates pin; see
core/generate.py ``_pick_rows`` / ``_sample_window_core`` /
``_verify_sample_core``).

PRNG contract — a request's token stream is a pure function of its seed:

* the base key is the host-side Threefry derivation
  ``[seed >> 32, seed & 0xffffffff]`` (:func:`base_key`), numerically
  identical to ``jax.random.PRNGKey(seed)`` but computed with numpy so
  submit never dispatches a device program;
* the token at generated index ``n`` is picked with
  ``fold_in(base_key, n)`` — the index, not the window phase, owns the
  key, so decode-ahead width, dense/paged layout, engine restarts, and
  router failover replays all consume the identical key schedule (the
  speculative path derives its accept/residual draws from the same
  ``fold_in`` family; see ``_verify_sample_core``).

:func:`first_pick` is the ONE module-level jitted first-token pick every
engine shares for prefill-miss, prefix-cache-hit, and paged-extend
landings: hit and miss run the same program over the same stored logits,
so a sampled request's first token is bit-identical either way — which
is what makes the prefix cache sampling-safe (it stores the
deterministic prefill logits, never a sampled token).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.core.generate import _pick_rows


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Validated per-request sampling config.

    ``temperature == 0`` is greedy (argmax; ``top_p``/``top_k``/``min_p``
    must be 0 and the seed is inert), ``temperature > 0`` samples the
    tempered distribution, optionally truncated to the ``top_k``
    highest-logit tokens, nucleus-filtered by ``0 < top_p < 1`` (top-k
    applies first, like the offline generator), and/or min-p-filtered by
    ``0 < min_p <= 1`` (tokens below ``min_p * max_prob`` cut, applied
    last; ``min_p = 1`` keeps only the argmax).  ``seed`` fully
    determines the request's token stream at fixed params/prompt —
    submit the same seed twice and the streams are token-identical;
    best-of-n is "same prompt, n seeds" (examples/11_sampling.py).
    """

    temperature: float = 0.0
    top_p: float = 0.0
    top_k: int = 0
    min_p: float = 0.0
    seed: int = 0

    def __post_init__(self):
        t, p, k, s = self.temperature, self.top_p, self.top_k, self.seed
        mp = self.min_p
        if not (isinstance(t, (int, float)) and np.isfinite(t) and t >= 0):
            raise ValueError(
                f"temperature must be a finite float >= 0, got {t!r}")
        if not (isinstance(p, (int, float)) and 0.0 <= float(p) <= 1.0):
            raise ValueError(f"top_p must be in [0, 1], got {p!r}")
        if p and t == 0:
            raise ValueError(
                "top_p filters a SAMPLING distribution; set temperature > 0")
        if (not isinstance(k, (int, np.integer)) or isinstance(k, bool)
                or int(k) < 0):
            raise ValueError(f"top_k must be an int >= 0, got {k!r}")
        if k and t == 0:
            raise ValueError(
                "top_k filters a SAMPLING distribution; set temperature > 0")
        if not (isinstance(mp, (int, float)) and 0.0 <= float(mp) <= 1.0):
            raise ValueError(f"min_p must be in [0, 1], got {mp!r}")
        if mp and t == 0:
            raise ValueError(
                "min_p filters a SAMPLING distribution; set temperature > 0")
        if not isinstance(s, (int, np.integer)) or isinstance(s, bool):
            raise ValueError(f"seed must be an int, got {s!r}")
        if not 0 <= int(s) < (1 << 64):
            raise ValueError(f"seed must fit in uint64, got {s}")

    @property
    def sampled(self) -> bool:
        return self.temperature > 0.0

    def key(self) -> np.ndarray:
        """The request's (2,) uint32 Threefry base key."""
        return base_key(self.seed)

    def to_dict(self) -> dict:
        """Strict-JSON form (plain floats/ints) — what the request
        journal persists (serving/journal.py).  Round-trips exactly
        through :meth:`from_dict`: the stream is a pure function of
        these five numbers, which is what makes crash replay
        token-identical."""
        return {"temperature": float(self.temperature),
                "top_p": float(self.top_p), "top_k": int(self.top_k),
                "min_p": float(self.min_p), "seed": int(self.seed)}

    @classmethod
    def from_dict(cls, d: dict) -> "SamplingParams":
        """Rebuild from :meth:`to_dict` output (re-validated)."""
        return cls(**d)


#: The default: greedy decode, seed inert.
GREEDY = SamplingParams()


def base_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` computed on the HOST with numpy —
    the same ``[hi32, lo32]`` uint32 pair, derived without dispatching
    (submit-path code must never pay a device program)."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


@jax.jit
def first_pick(logits, temps, topps, topks, minps, keys, pos):
    """The shared first-token pick program: fold each row's base key at
    its generated index (0 for a fresh request) and pick with the same
    data-driven :func:`_pick_rows` the decode window uses (every row
    counts as decoding, so a greedy landing computes argmax alone and a
    sampled one sorts only for top-k / top-p).  Module-level jit: every
    engine in the process shares one compilation per shape (top-k and
    min-p ride the ``topks``/``minps`` DATA planes), and prefix-cache
    hit/miss paths are bit-identical by construction.
    Returns ``((B,) int32 token, (B,) float32 logprob)``."""
    step_keys = jax.vmap(jax.random.fold_in)(keys, pos)
    return _pick_rows(logits, temps, topps, topks, minps, step_keys)
