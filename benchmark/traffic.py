"""The one general traffic generator.  A traffic mix is a data file under
``benchmark/traffic/``; this module turns its parameters into requests.

Lengths and arrival offsets are drawn from the file's own ``schedule_seed``,
so every run of a cell offers the same requests at the same instants: the
traffic is a replayed trace.  ``--seed`` makes only the token ids (and the
weights).  The arithmetic follows ``serving/traces.py`` (exponential gaps
for Poisson arrivals, clipped heavy-tailed lengths) without importing it.

Parameters of a serving mix (all but ``loop`` optional):

    loop            "closed" (clients that wait for a reply) | "open"
    schedule_seed   seed of lengths and arrivals
    prompt_len      {"dist": "lognormal", "median", "sigma", "min", "max"}
    max_new         | {"dist": "uniform", "min", "max"} | {"dist": "fixed", "value"}
    arrivals        {"process": "poisson", "rate_per_s"}
                    | {"process": "gamma", "rate_per_s", "cv"}  (cv > 1: bursts)
                    | {"process": "onoff", "rate_per_s", "burst_factor",
                       "period_s", "burst_s"}
    rate_scale      multiplies the rate by scaling every offset (default 1)
    clients         closed loop: number of clients
    prefix_groups, prefix_len   requests of one group share their first
                    ``prefix_len`` tokens (0: nothing shared)
"""

from __future__ import annotations

import numpy as np


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    if dist == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def draw_offsets(rng: np.random.Generator, spec: dict, horizon_s: float) -> np.ndarray:
    """Arrival offsets in seconds from the start of the trace, at the
    spec's own rate, covering ``horizon_s``."""
    rate, process = float(spec["rate_per_s"]), spec["process"]
    n = int(rate * horizon_s * 1.5) + 64
    if process == "poisson":
        gaps = rng.exponential(1.0 / rate, n)
    elif process == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        gaps = rng.gamma(shape, 1.0 / (rate * shape), n)
    elif process == "onoff":
        # bursts of burst_factor x the quiet rate for burst_s of every
        # period_s, with the mean held at rate_per_s
        f, period, on = float(spec["burst_factor"]), float(spec["period_s"]), float(spec["burst_s"])
        quiet = rate * period / (on * f + (period - on))
        t, out = 0.0, []
        while t < horizon_s * 1.5 + 1:
            r = quiet * f if (t % period) < on else quiet
            t += rng.exponential(1.0 / r)
            out.append(t)
        return np.asarray(out)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return np.cumsum(gaps)


def open_schedule(traffic: dict, horizon_s: float) -> dict:
    """The replayed trace of an open loop: ``due`` (seconds), ``prompt_len``
    and ``max_new`` for every request due within ``horizon_s``."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    scale = float(traffic.get("rate_scale", 1.0))
    # the unscaled trace is drawn long enough that its scaled form covers
    # the horizon, so a sweep replays one trace at several speeds
    due = draw_offsets(rng, traffic["arrivals"], horizon_s * max(scale, 1.0)) / scale
    n = len(due)
    sched = {"due": due, "prompt_len": draw_lengths(rng, traffic["prompt_len"], n),
             "max_new": draw_lengths(rng, traffic["max_new"], n)}
    keep = due < horizon_s
    return {k: v[keep] for k, v in sched.items()}


def closed_tables(traffic: dict, per_client: int) -> dict:
    """A closed loop's work: for each client its own list of requests, and
    the length its first answer is cut to so that clients start staggered."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    c = int(traffic["clients"])
    p = draw_lengths(rng, traffic["prompt_len"], c * per_client).reshape(c, per_client)
    m = draw_lengths(rng, traffic["max_new"], c * per_client).reshape(c, per_client)
    m[:, 0] = 1 + np.floor(rng.random(c) * m[:, 0]).astype(np.int64)
    return {"prompt_len": p, "max_new": m}


def prompt_tokens(seed: int, index: int, length: int, vocab: int,
                  traffic: dict | None = None) -> np.ndarray:
    """Token ids of request ``index`` under ``--seed``: never 0 (the pad)."""
    toks = np.random.default_rng([int(seed), int(index)]).integers(1, vocab, length)
    groups = int((traffic or {}).get("prefix_groups", 0))
    if groups:
        n = min(int(traffic["prefix_len"]), length)
        shared = np.random.default_rng([int(seed), 1 << 40, index % groups])
        toks[:n] = shared.integers(1, vocab, n)
    return toks.astype(np.int32)
