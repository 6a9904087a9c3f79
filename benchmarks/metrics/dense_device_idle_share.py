"""``device_idle_share`` for cells whose rate is ``tokens_per_s_per_chip`` (a
per-layer metric names ONE end-to-end metric it moves, so the reader has a
second name)."""

from harness import spec

read = spec.load_module("metrics", "device_idle_share").read
