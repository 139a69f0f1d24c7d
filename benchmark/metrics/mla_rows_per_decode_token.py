"""Kernels, decode: latent rows a decode token attends over, i.e. the mean
depth of the decode queries: `mla_rows_read_total` (the program's count, from
host-side depths, over decode dispatches) over `decode_tokens_total`, inside
the window. The latter counts each request's first token too, which a prefill
chunk samples: the mean reads low by one part in a request's output length.
A program without the counter reads nothing."""


def read(run):
    c = run["window"]["counters"]
    rows, tokens = c.get("mla_rows_read_total"), \
        c.get("decode_tokens_total")
    if rows is None or not tokens:
        return None
    return rows / tokens
