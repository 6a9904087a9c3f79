"""Layer: expert layer. The busiest expert's assignments over the mean
expert's, the largest over the layers, averaged over the window's
dispatches — from the step's own ``expert_counts`` counter (the program's
buffers). 1 = even loads; the grouped matmul's tiles and a later
expert-parallel exchange pay for the excess. None for a system that
reports no counts."""


def read(ctx):
    return getattr(ctx["system"], "load_max_over_mean", None)
