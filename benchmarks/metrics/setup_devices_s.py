"""Layer: entry points. The accelerator runtime's start-up: the harness's
clock round its first ``jax.devices()``, made before any program code
runs (``run.py``'s ``devices_s``). The machine's part of ``setup_s``, the
number the program's parts are to be read against."""


def read(ctx):
    return ctx["spans"].get("devices_s")
