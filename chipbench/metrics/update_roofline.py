"""Tile kernels: percent of the roofline reached by the Cholesky trailing
update, the ``_update`` body's programs in the device trace."""
from chipbench.record import kernel_roofline


def read(rec):
    return kernel_roofline(rec, "_update")
