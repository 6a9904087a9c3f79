"""Process start to the window's ``t0``: imports, native library, data,
table and pass build, compile or cache load, warm-up. Host clock."""


def read(ctx):
    return ctx["setup_s"]
