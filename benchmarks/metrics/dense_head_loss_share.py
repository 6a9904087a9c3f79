"""Layer: dense model step. Share of the traced operation time spent in final
LN, vocabulary projection and the loss (``pt.head_loss`` in the model,
``pt.loss`` round the trainer's loss function) (``harness/scopes.py``); None
for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.head_loss", "pt.loss")
