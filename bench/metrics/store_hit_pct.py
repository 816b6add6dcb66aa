"""Block store (datapath/blockstore.py): hits of the decoded and the
pre-filtered tiers over their hits and misses, as changes of
`BlockStore.stats()` over the window."""


def read(r):
    d = r.window
    n = d["store_hits"] + d["store_misses"]
    return 100.0 * d["store_hits"] / n if n else None
