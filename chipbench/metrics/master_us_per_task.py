"""Master: host microseconds per task spent spawning, which is the front
end and the dependence analysis (the harness's ``spawn`` span around the
spawn loop, over the tasks spawned in the window)."""


def read(rec):
    if not rec.tasks:
        return None
    return rec.spans["spawn"] / rec.tasks * 1e6
