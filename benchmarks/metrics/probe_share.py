"""Layer: sparse pull. Share of the traced operation time spent in the in-graph
key->row probe (``pt.probe``: both cuckoo bucket gathers and the sentinel
select) (``harness/scopes.py``); None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.probe")
