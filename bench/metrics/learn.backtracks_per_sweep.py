"""Armijo backtracks per KrK-Picard sweep in the window: the learning
engine's ``learning.backtracks`` over its ``learning.sweeps`` counter."""


def read(r):
    sweeps = r.counters.get("learning.sweeps", 0)
    if not sweeps:
        return None
    return r.counters.get("learning.backtracks", 0) / sweeps
