"""Routed experts: of the experts this chip holds, the share that at least
one token of a decode dispatch chose (`moe_experts_hit_total` over
`moe_expert_slots_total`: held experts x routed layers a decode dispatch),
inside the window. An expert no token chose is not read: it is what the
family's `work.py` charges a decode step's expert bytes by. A program
without the counters reads nothing."""


def read(run):
    c = run["window"]["counters"]
    hit, slots = c.get("moe_experts_hit_total"), \
        c.get("moe_expert_slots_total")
    if hit is None or not slots:
        return None
    return 100.0 * hit / slots
