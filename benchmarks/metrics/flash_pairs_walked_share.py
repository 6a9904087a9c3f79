"""Layer: kernels. Block pairs the flash kernels' grids walk over the pairs
of the whole ``nq x nk`` rectangle, summed over the ``flash_attention``
calls that set-up traced (the step's; in the held cells the router
balance's forward too, the same calls) — from the ``pairs_walked`` and
``pairs_rectangle`` counts of the program's ``pt.flash.operands`` spans
(``core/profiler.host_spans``; one span a call a trace) that start before
the window's ``t0``. A causal call that walks only the pairs its mask
leaves reads (n + 1) / 2n at n x n blocks: 0.5625 at 4096 positions in
512-blocks; a bidirectional call, or a causal one that steps through the
rectangle, 1.0. None for a program whose spans carry no such counts."""


def read(ctx):
    from paddle_tpu.core import profiler

    if not hasattr(profiler, "host_spans"):
        return None
    t0 = ctx["window"]["t0"]
    calls = [s.counts for s in profiler.host_spans()
             if s.name == "pt.flash.operands" and s.t0 < t0
             and "pairs_walked" in s.counts and "pairs_rectangle" in s.counts]
    rectangle = sum(c["pairs_rectangle"] for c in calls)
    return sum(c["pairs_walked"] for c in calls) / rectangle \
        if rectangle else None
