"""Operations and bytes of the two decode-path pieces the MiMo-V2-Flash
configuration adds, for their roofline shares.  Each function counts what
the ALGORITHM needs, from the configuration's widths and the program's
counters, never what an implementation happens to do: the expert layer is
charged each hit expert's three matrices once per call that gave it a pair
(the grouped product may fetch a matrix again for a second tile of rows),
the global layers' pages at the model's 192-wide keys (the pool stores them
padded to 256), so a share errs low and cannot pass 100% for that.  Peaks
and the roofline arithmetic are ``benchmark/roofline.py``'s.
"""

from __future__ import annotations

BF16 = 2


def expert_ffn_cost(pairs: int, hits: int, cfg: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the held experts' grouped products over a
    measured window.  ``pairs`` is the device's ``expert_load`` summed over
    layers and held experts: (token, choice) pairs computed, each through
    gate, up and down.  ``hits`` is its ``expert_hits``: (call, layer,
    expert) triples in which the expert got at least one pair, each of which
    has to read that expert's three matrices (3 x 4096 x 2048 x 2 B = 50.3
    MB) once.  A pair reads its token's row and writes its output row."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = 2.0 * 3 * pairs * d * f
    nbytes = 3.0 * hits * d * f * BF16 + 2.0 * pairs * d * BF16
    return ops, nbytes


def global_decode_cost(pages_read: int, row_steps: int, cfg: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the decode window's paged-attention calls
    over a measured window.  ``pages_read`` is the program's counter: pages
    the global layers' steps read, summed over decoding rows and global
    layers; a token of a page is 4 KV heads x (192 + 128) x 2 B = 2560 B.
    ``row_steps`` (decoding rows x steps) times the global layers gives the
    queries read and outputs written."""
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    heads, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = sum(1 for kind in cfg["hybrid_layer_pattern"] if not kind)
    tokens = pages_read * cfg["engine"]["kv_page_size"]   # per (row, layer)
    ops = 2.0 * heads * tokens * (dk + dv)                 # QK^T and PV
    nbytes = (tokens * hkv * (dk + dv) * BF16
              + row_steps * layers * heads * (dk + dv) * BF16)
    return ops, nbytes
