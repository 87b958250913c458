"""Share of the traced window in which a collective-permute runs on a
device and no other operation does, mean over devices, in % (device
trace). Nothing to read where no collective-permute ran."""

from chipbench import reduce

PREFIX = "collective-permute"


def read(rec):
    if rec.trace is None:
        return None
    if not any(name.startswith(PREFIX) for ops in rec.trace.devices.values()
               for _, _, name in ops):
        return None
    return 100.0 * reduce.exposed_share(rec.trace, PREFIX)
