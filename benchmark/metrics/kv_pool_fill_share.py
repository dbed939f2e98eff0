"""Mean share of the page pool's pages that are allocated, sampled after
every engine step of the window: the program's ``ServingStats.pool_sample``
(live requests' pages plus the pages the radix trie retains)."""


def read(r):
    return r.counters.get("kv_pool_fill_share")
