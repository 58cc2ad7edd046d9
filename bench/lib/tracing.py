"""The benchmark's own spans, and one traced window."""
from __future__ import annotations

import contextlib
import shutil
import sys
import tempfile
import threading
import time

from bench.lib import trace as trace_lib


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def profiled(span_names, window_after: float = 0.0, window_for: float = 0.0):
    """Trace what runs inside; yields a holder whose ``.trace`` is the
    reduced ``Trace`` once the block has closed. The ``window`` span is the
    whole block, or, with ``window_for``, a stretch of that many seconds
    that opens ``window_after`` seconds in (a steady part of one long
    call). The raw trace goes to a temporary directory and is deleted."""
    import jax
    holder = type("Traced", (), {})()
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    stop = threading.Event()

    def marker():
        if stop.wait(window_after):
            return
        with span(trace_lib.WINDOW_SPAN):
            stop.wait(window_for)

    th = None
    jax.profiler.start_trace(tmp)
    try:
        if window_for:
            th = threading.Thread(target=marker, daemon=True)
            th.start()
            yield holder
        else:
            with span(trace_lib.WINDOW_SPAN):
                yield holder
    finally:
        stop.set()
        if th is not None:
            th.join()
        jax.profiler.stop_trace()
    try:
        path = trace_lib.find_xplane(tmp)
        t0 = time.perf_counter()
        holder.trace = trace_lib.Trace.from_file(path, span_names)
        print(f"bench: trace read in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def breakdown(tr) -> dict:
    return {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
