"""1 - (union of device-op intervals / the traced window), mean over
the cell's devices, in % (device trace)."""

from chipbench import reduce


def read(rec):
    if rec.trace is None or not rec.trace.devices:
        return None
    return 100.0 * reduce.idle_share(rec.trace)
