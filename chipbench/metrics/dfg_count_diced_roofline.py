"""Roofline share of the fused window-count kernel ``dfg_count_diced``:
the least time of its calls over their summed device time in the trace.

The least time of one call is the bytes the windowed query must read
(``roofline.dfg_window_bytes``: every directly-follows pair of the log,
log events minus cases, at 16 bytes, plus the int32 result) over the
device's peak bytes/s.  The number of calls is the delta of the program's
``kernel_seconds{kernel=dfg_count_diced}`` count over the traced window."""

from chipbench.roofline import dfg_window_bytes, least_time_s

# the kernel's custom call carries the name of its jitted entry
PATTERN = r"^%dfg_count_diced(\.\d+)? = "


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.kernel_seconds(PATTERN)
    calls = run.counters.get("kernel_seconds{kernel=dfg_count_diced}.count", 0.0)
    if device_s <= 0 or calls <= 0:
        return None
    pairs = run.log_events - run.log_traces
    a = int(run.cell.config["num_activities"])
    least = calls * least_time_s(dfg_window_bytes(pairs, a), run.peaks())
    return 100.0 * least / device_s
