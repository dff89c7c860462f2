"""Device: percent of the traced window in which no operation ran on the
device, averaged over the chips used."""
from chipbench import tracing


def read(rec):
    if rec.trace is None:
        return None
    busy = tracing.busy_seconds(rec.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / rec.trace.window_s)
