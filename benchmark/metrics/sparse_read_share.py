"""Share of the decoding rows' live blocks that decode attention read: the
program's ``ServingStats`` counters ``sparse_blocks_read`` over
``sparse_blocks_live`` (per row, sparse layer and KV head, summed over the
window's decode steps).  100 on a model that selects nothing."""


def read(r):
    live = r.counters.get("sparse_blocks_live")
    read_ = r.counters.get("sparse_blocks_read")
    return 100.0 * read_ / live if live and read_ is not None else None
