"""Requested rows per device call in the window: the program's
``serving.requested_rows`` over its ``service.device_calls`` counter
(above 1 when tenants' requests coalesce)."""


def read(r):
    calls = r.counters.get("service.device_calls", 0)
    if not calls:
        return None
    return r.counters.get("serving.requested_rows", 0) / calls
