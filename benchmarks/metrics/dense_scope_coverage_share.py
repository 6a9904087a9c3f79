"""``scope_coverage_share`` for cells whose rate is ``tokens_per_s_per_chip``
(``pt.embed`` is reported here only). A per-layer metric names ONE end-to-
end metric it moves, so the reader has a second name."""

from harness import spec

read = spec.load_module("metrics", "scope_coverage_share").read
