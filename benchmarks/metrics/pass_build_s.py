"""Layer: pass build / residency. Host clock around
``HbmEmbeddingCache.begin_pass`` (dedup, cuckoo build, host table export,
upload), closed by ``block_until_ready`` on the state."""


def read(ctx):
    return ctx["spans"].get("pass_build_s")
