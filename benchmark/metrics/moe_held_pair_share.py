"""Routed experts: the share of the routers' token-expert pairs that fell on
experts this chip holds. The program's routed layers hand each dispatch's
counts back with its probabilities, and the engine adds them up
(`moe_pairs_held_total` over `moe_pairs_routed_total`: tokens x experts a
token x routed layers, of the dispatches whose counts are read: every decode
step and each prompt's last chunk), inside the window. Held experts over all
experts when routing is even: 6.25 % for 12 of 192. It is what the family's
`work.py` charges a step's expert operations by. A program without the
counters reads nothing."""


def read(run):
    c = run["window"]["counters"]
    held, routed = c.get("moe_pairs_held_total"), \
        c.get("moe_pairs_routed_total")
    if held is None or not routed:
        return None
    return 100.0 * held / routed
