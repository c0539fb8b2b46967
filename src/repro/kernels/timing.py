"""Wall-clock timing hook for the Pallas kernel entry points.

Each kernel entry point (``dfg_count``, ``dfg_count_diced``,
``segment_count``, and the ``align_dp`` kernel call behind its numpy/pallas
dispatch) is wrapped once at import time; every call lands in the
process-global :func:`repro.obs.kernel_registry` as a
``kernel_seconds{kernel=<name>}`` histogram.  Kernels are process-wide
jitted callables shared by every engine, so their timings live in the
global registry rather than any per-engine one — the engine merges both
in ``metrics_snapshot()``.

The wrapper blocks on the device result (``block_until_ready``) so the
histogram records true wall time, not async dispatch time; callers
consume the result synchronously anyway, so nothing is serialized that
was not already.  The first observation of a jitted kernel includes its
compile time — that *is* the wall time the triggering query paid.
"""

from __future__ import annotations

import functools
from time import perf_counter

from repro.obs.metrics import kernel_registry

__all__ = ["timed_kernel"]


def timed_kernel(name: str, fn):
    """Wrap a kernel entry point; records wall seconds per call."""
    hist = kernel_registry().histogram("kernel_seconds", kernel=name)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        t0 = perf_counter()
        out = fn(*args, **kwargs)
        ready = getattr(out, "block_until_ready", None)
        if ready is not None:
            out = ready()
        hist.observe(perf_counter() - t0)
        return out

    wrapper.__wrapped_kernel__ = fn
    return wrapper
