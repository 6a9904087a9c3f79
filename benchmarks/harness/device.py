"""What the run ran on, and the one table of peaks.

Peaks are published numbers keyed by ``device_kind`` as JAX reports it; a
device that is not in the table is an error, never a default."""

from __future__ import annotations

from typing import Any, Dict, List

#: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s bf16,
#: 819 GB/s HBM, 16 GB HBM. ``measured_matmul_flops`` is this repo's own
#: ceiling point: 20 chained 8192^3 bf16 matmuls, 185.6 TFLOP/s (chip run,
#: PR 21) — kept for reading utilizations, used by no metric.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "measured_matmul_flops": 185.6e12},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to {__name__}.PEAKS with its source")
    return PEAKS[device_kind]


def working_set_bytes(devices: List[Any]) -> int:
    """HBM taken NOW on the fullest of ``devices``: ``bytes_in_use`` (live
    arrays) plus ``bytes_reserved``. This TPU runtime RESERVES a loaded
    program's temporaries instead of allocating them, so ``bytes_in_use``
    alone leaves them out: an ERNIE step that compiles to 12.9 GiB showed
    1.79 GiB in use and 11.58 GiB reserved, and a probe program with a 3 GiB
    temporary showed exactly 3 GiB reserved (chip runs, PR 23). 0 where the
    backend reports nothing, as the CPU does."""
    most = 0
    for d in devices:
        stats = d.memory_stats() or {}
        most = max(most, int(stats.get("bytes_in_use", 0))
                   + int(stats.get("bytes_reserved", 0)))
    return most


def live_peak_bytes(devices: List[Any]) -> int:
    """Largest ``peak_bytes_in_use`` over ``devices``: the most that live
    arrays ever took in this process, set-up included (a table staged
    through one device shows here), programs' temporaries not."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_facts(devices: List[Any], hbm_window_bytes: int) -> Dict[str, Any]:
    """``memory_peak_bytes``: the larger of the window's working set
    (``hbm_peak_gib``, steady from run to run) and the live arrays'
    lifetime peak (which depends on when set-up's transfers free their
    staging): the most the process is known to have taken."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(hbm_window_bytes,
                                     live_peak_bytes(devices))}
