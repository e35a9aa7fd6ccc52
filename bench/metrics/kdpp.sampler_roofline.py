"""The k-DPP sampler's share of its roofline: the least time the chip
needs for the operations and bytes of the calls the device trace holds
whole (``bench/held.py``; ``bench/kdpp_counts.py``: one ESP table a call,
the conditional draw and the chain rule at the slate width k a row) over
the device's busy time in them, at the ``bench/peaks.py`` row of the
device. Nothing when the window made no call or the trace holds none
whole."""

from bench import counts, kdpp_counts, peaks
from bench.held import held


def read(r):
    w = r.work
    if not w.get("calls") or not len(w.get("sizes", ())):
        return None
    got = held(r)
    if got is None:
        return None
    calls, _, busy_s = got
    rows = len(w["sizes"]) // w["calls"] * calls
    flops, nbytes = kdpp_counts.window_work(w["factor_sizes"], w["k_max"],
                                            rows, calls)
    share, _ = counts.roofline_share(flops, nbytes, busy_s,
                                     peaks.peaks(r.device_kind))
    return 100.0 * share
