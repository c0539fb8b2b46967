"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals / traced span."""


def read(run):
    return run.idle_share_pct()
