"""Trace capture: a few seconds of steady state under ``jax.profiler``.

Only the process that holds the chip can trace it, so this runs wherever the
program runs (the driver's own process, or its server child). The trace
directory is a fixed path inside the checkout, emptied before each capture.
Host spans are ``jax.profiler.TraceAnnotation``s whose names start with
``SPAN_PREFIX``: they land in the same trace, on the same clock as the device
events, and the reduction attributes device-idle gaps to them.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil

SPAN_PREFIX = "bench."


def span(name: str):
    """A host span of the benchmark's own, visible in the captured trace."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def capture(trace_dir: str):
    """Trace everything inside the block; yields a dict whose ``"path"`` is
    the ``.xplane.pb`` once the block has ended."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    out = {"path": None}
    jax.profiler.start_trace(trace_dir)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    out["path"] = max(found, key=os.path.getmtime)
