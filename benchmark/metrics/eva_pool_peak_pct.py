"""Cache manager: the pool's own high-water mark over its capacity (the
gauge `kv_pool_blocks_live` of the program's `KVPool`, every block handed
out and not yet returned, over `capacity_blocks`). For a net whose attention
recycles pages, what a request holds is not `ceil(length / kv_block)`, which
is what `kv_pool_peak_pct` counts from the rows: an EvaByte request gives its
window's exact pages back when the window closes and keeps the chunk
summaries, so the pool's own count is read. Nothing is in the trie there (it
adopts no recycled page), so the gauge counts resident requests alone."""


def read(run):
    w = run["window"]
    live, cap = w.get("pool_live_max"), w.get("capacity_blocks")
    if not cap or live is None:
        return None
    return 100.0 * live / cap
