"""Kernels, decode: how much of the block tables its decode dispatches name a
latent net's step really reads. The program counts, from host-side depths, the
pages in every decode dispatch's tables (`mla_pages_bucket_total`: slots x the
table bucket) and the pages read (`mla_pages_read_total`: those holding a row
a fed slot attends over where the fused latent read engages, else the
bucket's); read over named, inside the window. 100 means the gather at the
bucket's width. A program without the counters reads nothing."""


def read(run):
    c = run["window"]["counters"]
    named, got = c.get("mla_pages_bucket_total"), \
        c.get("mla_pages_read_total")
    if named is None or got is None or named <= 0:
        return None
    return 100.0 * got / named
