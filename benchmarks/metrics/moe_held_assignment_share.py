"""Layer: expert layer. Assignments that landed on an expert this chip
holds over all assignments (tokens x experts a token x expert layers),
averaged over the window's dispatches — from the step's own
``held_assignments`` counter (the program's buffers). Held / router width
when loads are even (16 / 256 = 0.0625). None for a system that reports
no such counter."""


def read(ctx):
    return getattr(ctx["system"], "held_assignment_share", None)
