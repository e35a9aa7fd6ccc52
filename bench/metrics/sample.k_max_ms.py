"""Mean host time of the facade's phase-2 budget decision per sampling
call: the program's ``dpp.sample.k_max`` spans on the profiler's clock.
Nothing when the trace holds no such span."""


def read(r):
    spans = [e - s for n, s, e in r.trace.host if n == "dpp.sample.k_max"]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e6
