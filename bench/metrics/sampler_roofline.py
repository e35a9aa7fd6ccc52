"""The sampler's share of its roofline: the least time the chip needs for
the algorithmic operations and bytes of every draw of the window
(``bench/counts.py``, with the drawn size of each row) over the device's
busy time. The draws are compute-bound at these sizes: the bound is the
bf16 peak of the ``bench/peaks.py`` row of the device."""

from bench import counts, peaks


def read(r):
    w = r.work
    if not w.get("calls"):
        return None
    flops, nbytes = counts.window_work(w["factor_sizes"], w["sizes"],
                                       w["calls"], w["k_max"])
    share, _ = counts.roofline_share(flops, nbytes, r.trace.busy_s,
                                     peaks.peaks(r.device_kind))
    return 100.0 * share
