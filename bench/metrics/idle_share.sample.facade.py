"""Share of the sampling window in which the device idles while the host
runs the facade's own code: the idle gaps of the first device (between
its merged busy spans) that fall inside the program's ``dpp.sample``
host spans, over the window. The rest of ``idle_share.sample`` belongs to
the harness and to the wake-up after each block. Nothing when the trace
holds no ``dpp.sample`` span."""

from bench.trace import _merge, _minus


def read(r):
    facade = _merge([h for h in r.trace.host if h[0] == "dpp.sample"])
    if not facade:
        return None
    busy = _merge(r.trace.ops[min(r.trace.ops)])
    lo, hi = busy[0][0], busy[-1][1]
    inside = [(max(s, lo), min(e, hi)) for s, e in facade if e > lo and s < hi]
    return 100.0 * _minus(inside, busy) / 1e9 / r.trace.window_s
