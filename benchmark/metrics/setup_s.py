"""Process start to ready: imports, weights, engine, warm-up of the cell's
shapes (compile, or cache load). The reference's time is not in it."""


def read(run):
    return run["setup_s"]
