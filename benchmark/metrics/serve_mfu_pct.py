"""Whole step: model FLOPs of every token prefilled or decoded inside the
window (matmuls of the layers held, attention at its real depths, the head
where a token is sampled; nothing recomputed or padded counts) over the
window's seconds times the chip's bf16 peak."""
from benchmark.harness import facts


def read(run):
    if run["peaks"] is None:
        return None
    w = run["window"]
    t0, t1 = w["t0"], w["t0"] + w["seconds"]
    depths = facts.decode_depths(run["rows"], t0, t1)
    fd, _ = facts.decode_work(run, depths, 1, t0, t1)
    fp, _ = facts.prefill_work(run, facts.chunks(run, t0, t1))
    if fd + fp <= 0:
        return None
    return 100.0 * (fd + fp) / (w["seconds"]
                                * run["peaks"]["bf16_flops_per_s"]
                                * run["device"]["count"])
