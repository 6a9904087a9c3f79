"""Layer: sparse push. Device time of operations that touch the whole
table over the time of all operations. An operation touches the whole
table when a result or operand of its HLO instruction has a leading
dimension of at least the shard's row count. The shapes are read from the
instruction text the profiler records as the operation's name (or, where
a name carries none, from the compiled HLO instruction of that name) —
never guessed from a fusion's number."""


def read(ctx):
    red, rows = ctx["trace"], ctx["system"].table_rows
    if not red or not rows:
        return None
    from harness import hlo, trace

    compiled = hlo.instruction_dims(ctx["hlo_text"])
    total = sweep = 0.0
    for event_name, s in red["op_self_s"].items():
        name = trace.op_name(event_name)
        lead = max(hlo.instruction_dims(event_name).get(name, 0),
                   compiled.get(name, 0))
        total += s
        if lead >= rows:
            sweep += s
    return sweep / total if total else None
