"""Process-wide spans: pauses of Python's cyclic garbage collector.

A collection holds the interpreter lock, so it stops the event loop, every
lane and every thread feeding the device for its length.  One
``gc.callbacks`` hook per process (:func:`install_gc_hook`, called by every
:class:`repro.query.QueryEngine`) observes each pause into the
process-global :func:`repro.obs.kernel_registry` as
``process_gc_pause_seconds{generation=<k>}``, and while a profiler session
is active emits ``repro.gc.gen<k>`` between the hook's ``start`` and
``stop``, so a collection that leaves the device idle shows as one.
"""

from __future__ import annotations

import gc
import threading
from time import perf_counter

from .metrics import kernel_registry
from .trace import profile_begin, profile_end

__all__ = ["install_gc_hook"]

_install_lock = threading.Lock()
_hists = None
_spans = ("gc.gen0", "gc.gen1", "gc.gen2")
# the collection under way: open profiler span, start stamp.  Collections
# never nest (the interpreter runs one at a time), so one slot serves all
# threads.
_open = [None, 0.0]


def _on_gc(phase: str, info: dict) -> None:
    k = info["generation"]
    if phase == "start":
        _open[1] = perf_counter()
        _open[0] = profile_begin(_spans[k])
    else:
        profile_end(_open[0])
        _open[0] = None
        _hists[k].observe(perf_counter() - _open[1])


def install_gc_hook() -> None:
    """Install the collector hook once per process; later calls do
    nothing."""
    global _hists
    with _install_lock:
        if _hists is not None:
            return
        reg = kernel_registry()
        _hists = [
            reg.histogram(
                "process_gc_pause_seconds",
                "Pauses of Python's cyclic garbage collector",
                generation=str(k),
            )
            for k in range(3)
        ]
        gc.callbacks.append(_on_gc)
