"""Layer: input path. Time the dispatch loop waited in the feeder
(``DevicePrefetcher``) over the window's elapsed time."""


def read(ctx):
    w = ctx["window"]
    return sum(w["feed_wait_s"]) / w["elapsed_s"]
