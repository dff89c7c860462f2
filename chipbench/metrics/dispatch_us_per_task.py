"""Executor waves: host microseconds per task inside ``rt.barrier()``,
which layers the waves, stacks the operands, dispatches and stores the
results (the harness's ``barrier`` span, over the tasks spawned in the
window).  The barrier does not wait for the device."""


def read(rec):
    if not rec.tasks:
        return None
    return rec.spans["barrier"] / rec.tasks * 1e6
