"""Layer: collectives. Bytes one participant moves per train step, from
the collectives of the compiled step's HLO (``harness/hlo.report``, the
ring estimate of ``tools/hlo_bytes.py``). A count, not a time."""


def read(ctx):
    if not ctx["hlo_text"] or ctx["chips"] < 2:
        return None
    from harness import hlo

    rep = hlo.report(ctx["hlo_text"],
                                            num_devices=ctx["chips"])
    return rep["wire_bytes_total"] / 1e6 / ctx["system"].steps_per_dispatch
