"""Layer: pass build / residency. Host clock around
``HbmEmbeddingCache.end_pass`` (device -> host table). Runs in traced runs
only; the other half of the stall between passes."""


def read(ctx):
    return ctx["spans"].get("pass_flush_s")
