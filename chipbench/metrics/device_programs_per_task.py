"""Executor waves: device programs that start in the traced window,
summed over the chips, per task spawned in the window.  Each eager
stack, slice or jitted body the host dispatches is one program, so this
counts the host's launches, whatever their size."""


def read(rec):
    if rec.trace is None or not rec.trace.devices or not rec.tasks:
        return None
    lo, hi = rec.trace.window
    started = sum(lo <= start < hi
                  for d in rec.trace.devices.values()
                  for _, start, _ in d.programs)
    return started / rec.tasks
