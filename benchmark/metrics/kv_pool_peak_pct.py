"""Cache manager: peak share of the pool's blocks (capacity: the program's
`KVPool.capacity_blocks`) held by resident requests, each counted from its
admission with its whole prompt (as the engine's admission gate reserves
it) plus the tokens it has released, until it is done. Blocks the prefix
trie keeps for finished prompts are reclaimable and not counted; the pool's
own high-water gauge, which counts them, is in the breakdown."""


def read(run):
    blk = run["geometry"]["kv_block"]
    events = []
    for r in run["rows"]:
        if r["admitted"] is None:
            continue
        held = -(-r["prompt_len"] // blk)
        events.append((r["admitted"], held))
        for j, t in enumerate(r["stamps"]):
            need = -(-(r["prompt_len"] + j + 1) // blk)
            if need > held:
                events.append((t, need - held))
                held = need
        if r["done"] is not None:
            events.append((r["done"], -held))
    peak = cur = 0
    for _, d in sorted(events):
        cur += d
        peak = max(peak, cur)
    cap = run["window"]["capacity_blocks"]
    return 100.0 * peak / cap if cap and events else None
