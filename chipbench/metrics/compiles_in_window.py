"""Executor waves: programs compiled or loaded from the compilation cache
while the window was open (JAX's backend-compile events).  Set-up warms
every shape up, so this reads 0 unless a solve meets a new shape."""


def read(rec):
    return float(rec.compiles_in_window)
