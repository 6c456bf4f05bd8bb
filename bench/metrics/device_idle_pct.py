"""device_idle_pct: 1 - (union of operation intervals on a device) /
(traced window), in percent, mean over the cell's devices.  Layer: device.
Device trace."""


def read(rec):
    if rec.trace is None or not rec.trace.devices:
        return None
    return 100.0 * rec.trace.idle_share()
