"""Operations and bytes of the three pieces the Nemotron-3-Super
configuration adds, for their roofline shares.  Each function counts what
the ALGORITHM needs, from the configuration's widths, the call's shape and
the program's counters, never what an implementation happens to do: the
chunk scan is charged its inputs and outputs in bf16 (the kernel takes
``dt x`` and gives ``y`` in float32), the decode update the decoding rows
only (the kernel reads and writes every row's state, idle or not), the
latent experts each hit expert's two matrices once per call that gave it a
pair; so a share errs low and cannot pass 100% for that.  Peaks and the
roofline arithmetic are ``benchmark/roofline.py``'s.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def ssm_scan_cost(heads: int, tokens: int, head_dim: int, cfg: dict,
                  sub: int = 256) -> tuple[float, float]:
    """``(operations, bytes)`` of ONE chunk-scan call of result shape
    ``(heads, tokens, head_dim)``, in the chunked form of the recurrence at
    sub-chunks of ``sub`` tokens: the causal half of C B^T (once a group)
    and of (.) (dt x) inside a sub-chunk, C S^T and (dt x)^T B against the
    head_dim x state state.  Bytes: x and y, B and C in bf16, dt in
    float32, the float32 state in and out."""
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    sub = min(sub, tokens)
    ops = (heads * tokens * (1.0 * sub * head_dim + 4.0 * n * head_dim)
           + g * tokens * 1.0 * sub * n)
    nbytes = (2.0 * heads * tokens * head_dim * BF16 + 2.0 * g * tokens * n * BF16
              + heads * tokens * F32 + 2.0 * heads * head_dim * n * F32)
    return ops, nbytes


def ssm_step_cost(row_steps: int, cfg: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the decode window's SSD updates over a
    measured window.  ``row_steps`` is the program's counter: decoding
    row-steps summed over the Mamba layers.  Each reads and writes its
    row's float32 state (128 x 64 x 128 x 4 B = 4.19 MB), reads x (bf16),
    dt (float32), B and C (bf16) and writes y (bf16); a head's update and
    output are 4 x head_dim x state operations."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    ops = row_steps * 4.0 * h * p * n
    nbytes = row_steps * (2.0 * h * p * n * F32 + 2.0 * h * p * BF16
                          + h * F32 + 2.0 * g * n * BF16)
    return ops, nbytes


def latent_expert_cost(pairs: int, hits: int, cfg: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the held experts' grouped products over a
    measured window.  ``pairs`` is the device's ``expert_load`` summed over
    layers and held experts: (token, choice) pairs computed, each through
    W1 and W2 in the latent.  ``hits`` is its ``expert_hits``: (call, layer,
    expert) triples in which the expert got at least one pair, each of which
    has to read that expert's two matrices (2 x 1024 x 2688 x 2 B = 11.0
    MB) once.  A pair reads its token's latent row and writes its output
    row."""
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    ops = 2.0 * 2 * pairs * lat * f
    nbytes = 2.0 * hits * lat * f * BF16 + 2.0 * pairs * lat * BF16
    return ops, nbytes
