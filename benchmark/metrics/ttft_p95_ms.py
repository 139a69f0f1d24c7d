"""95th percentile, over every request due in the window, of first output
token minus the time the request was *due* (host clock; `t_first_token` is
the program's stamp, the due time the harness's). A request that never
produced a token counts its whole wait up to the end of the drain."""
from benchmark.harness.stats import percentile


def read(run):
    end = run["window"]["t0"] + run["window"]["seconds"] \
        + run["window"]["drained_s"]
    return percentile([((r["first"] or end) - r["due"]) * 1e3
                       for r in run["rows"]], 95)
