"""Memory layer: bytes of tiles moved between devices per solve, counted
by the runtime's ``TileTraffic`` over the window's spawns, barriers and
drains (the reload of the inputs is not counted)."""


def read(rec):
    if rec.n_devices < 2 or not rec.solves:
        return None
    return rec.bytes_moved / rec.solves
