"""Operations and bytes of the three kernels the MiniCPM-SALA configuration
runs, for their roofline shares.  Each function counts what the ALGORITHM
needs for the call, from the call's shapes and the program's counters, never
what an implementation happens to do: the decode kernel is handed pages that
hold both KV heads and reads them whole (twice the bytes one head needs), the
prefill kernel walks every page up to the query (more pairs than the
selection has), so their shares err low and cannot pass 100% for that.
Peaks and the roofline arithmetic are ``benchmark/roofline.py``'s.
"""

from __future__ import annotations

BF16 = 2


def sparse_spec(cfg: dict) -> dict:
    return cfg["assumed_sizes"]["sparse_config"]


def selected_blocks(sp: dict) -> int:
    return sp["init_blocks"] + sp["topk"] + sp["window_size"] // sp["block_size"]


def keys_of_query(t: int, sp: dict) -> int:
    """Keys the query at position ``t`` attends to: its whole context within
    ``dense_len``, else its selected blocks, the last one up to itself."""
    if t + 1 <= sp["dense_len"]:
        return t + 1
    bs = sp["block_size"]
    return (selected_blocks(sp) - 1) * bs + t % bs + 1


def sparse_decode_cost(blocks_read: int, row_steps: int, cfg: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the window's paged-attention calls over a
    measured window.  ``blocks_read`` is the program's counter: pages handed
    to the kernel, summed over decoding rows, sparse layers and KV heads;
    a page's necessary bytes are ONE KV head's keys and values.
    ``row_steps`` (decoding rows x steps) times the sparse layers gives the
    queries read and outputs written."""
    sp = sparse_spec(cfg)
    d, heads = cfg["head_dim"], cfg["num_attention_heads"]
    group = heads // cfg["num_key_value_heads"]
    layers = sum(m == "minicpm4" for m in cfg["mixer_types"])
    tokens = blocks_read * sp["block_size"]           # per (row, layer, KV head)
    ops = 2.0 * 2 * group * tokens * d                # QK^T and PV of a group
    nbytes = 2.0 * tokens * d * BF16 + 2.0 * row_steps * layers * heads * d * BF16
    return ops, nbytes


def sparse_prefill_cost(starts: dict, chunk: int, cfg: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the extend program's sparse-attention
    calls: ``starts`` maps a chunk's first position to the number of chunks
    (times the sparse layers = kernel calls) that began there.  Every query
    of the call's shape is counted (the program computes the padded ones
    too), each against the keys its selection holds, causal inside its own
    block; K and V are read once over the live span."""
    sp = sparse_spec(cfg)
    d, heads, hkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = sum(m == "minicpm4" for m in cfg["mixer_types"])
    ops = nbytes = 0.0
    for start, n in starts.items():
        start = int(start)
        pairs = sum(keys_of_query(start + i, sp) for i in range(chunk))
        ops += n * layers * 2.0 * 2 * heads * pairs * d
        nbytes += n * layers * (2.0 * chunk * heads * d * BF16
                                + 2.0 * (start + chunk) * hkv * d * BF16)
    return ops, nbytes


def lightning_scan_cost(heads: int, tokens: int, d: int, sub: int = 256) -> tuple[float, float]:
    """``(operations, bytes)`` of ONE lightning chunk-scan call of result
    shape ``(heads, tokens, d)``, in the chunked form of the recurrence at
    sub-chunks of ``sub`` tokens: the causal half of Q K^T and of (.) V
    inside a sub-chunk, Q S and K^T V against the d x d state.  Bytes: q, k,
    v in and o out in bf16, the float32 state in and out."""
    sub = min(sub, tokens)
    ops = heads * tokens * (2.0 * sub * d + 4.0 * d * d)
    nbytes = 4.0 * heads * tokens * d * BF16 + 2.0 * heads * d * d * 4
    return ops, nbytes
