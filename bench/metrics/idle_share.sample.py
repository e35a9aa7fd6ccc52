"""Share of the sampling window in which the device ran no operation."""


def read(r):
    return 100.0 * r.trace.idle_share
