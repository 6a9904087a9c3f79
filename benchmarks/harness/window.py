"""The measured window: whole dispatches over the time those dispatches
took.

One loop runs warm-up and window without draining the device between
them. At most ``INFLIGHT`` dispatches are outstanding; the loop blocks on
the oldest, which is when that dispatch's completion time is read. Warm-up
ends at the first completion after ``MIN_WARM`` whose interval agrees with
the one before it to ``AGREE`` (the first dispatches compile or load
programs and fill the feed). That completion is ``t0``. The loop goes on
dispatching until a completion lands ``seconds`` after ``t0``, then drains;
the last completion is ``t1``. The rate is the work of the dispatches that
completed after ``t0`` over ``t1 - t0``: no division by the nominal window,
so nothing is quantised by it, and stalls stay in the rate. At every
completion inside the window the devices' working set is read
(``harness/device.working_set_bytes``); the largest is the window's HBM.

With ``trace_dir`` the loop drains after warm-up, starts the profiler,
takes ``t0`` on the host, and the traced window is the whole window (keep
``seconds`` short). Feed, dispatch and sync are wrapped in
``TraceAnnotation``s named ``bench.*`` so that the trace reduction can say
what the host was doing in a device gap.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, Dict, List, Optional

INFLIGHT = 3
MIN_WARM = 3
MAX_WARM = 12
AGREE = 0.02


def _warmed(done: List[float]) -> bool:
    if len(done) >= MAX_WARM:
        return True
    if len(done) < MIN_WARM:      # two intervals need three completions
        return False
    a, b = done[-2] - done[-3], done[-1] - done[-2]
    return abs(a - b) <= AGREE * max(a, b)


def measure(system, seconds: float, log, trace_dir: Optional[str],
            devices: List[Any]) -> Dict[str, Any]:
    import jax

    from harness.device import working_set_bytes

    now = time.perf_counter
    if trace_dir:
        span = lambda name: jax.profiler.TraceAnnotation("bench." + name)
    else:
        span = lambda name: contextlib.nullcontext()

    feeder = system.feeder()
    inflight: collections.deque = collections.deque()
    handles: List[Any] = []       # one per dispatch, in order
    done: List[float] = []        # completion time of dispatch j
    feed_s: List[float] = []      # host time spent waiting for the feed
    disp_s: List[float] = []      # host time spent in the dispatch call
    req_before: List[int] = []    # compile requests seen before dispatch j
    first = None                  # index of the window's first dispatch
    t0 = t1 = 0.0
    hbm = 0                       # largest working set seen in the window

    def complete_one() -> None:
        h = inflight.popleft()
        with span("sync"):
            jax.block_until_ready(h)
        done.append(now())
        if first is not None:
            nonlocal hbm
            hbm = max(hbm, working_set_bytes(devices))

    try:
        while True:
            with span("feed"):
                t = now()
                item = next(feeder)
                feed_s.append(now() - t)
            req_before.append(log.requests)
            with span("dispatch"):
                t = now()
                h = system.dispatch(item)
                disp_s.append(now() - t)
            handles.append(h)
            inflight.append(h)
            if len(inflight) > INFLIGHT:
                complete_one()
            if first is None:
                if _warmed(done):
                    if trace_dir:
                        while inflight:
                            complete_one()
                        # host spans come from TraceAnnotation; Python's
                        # own call tracer would slow the loop it observes
                        opts = jax.profiler.ProfileOptions()
                        opts.python_tracer_level = 0
                        jax.profiler.start_trace(trace_dir,
                                                 profiler_options=opts)
                        t0 = now()
                    else:
                        t0 = done[-1]
                    first = len(done)
            elif done and done[-1] - t0 >= seconds:
                break
        while inflight:
            complete_one()
        t1 = done[-1]
    finally:
        if trace_dir and first is not None:
            jax.profiler.stop_trace()
        feeder.close()

    n = len(done) - first
    return {
        "t0": t0, "t1": t1, "elapsed_s": t1 - t0,
        "dispatches": n, "warmup_dispatches": first,
        "handles": handles[first:],
        "done_s": [d - t0 for d in done[first:]],
        "warmup_done_s": [d - t0 for d in done[:first]],
        "feed_wait_s": feed_s[first:], "dispatch_s": disp_s[first:],
        "compiles_in_window": log.requests - req_before[first],
        "hbm_window_bytes": hbm,
    }
