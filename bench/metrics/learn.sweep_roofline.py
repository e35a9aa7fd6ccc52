"""The learning sweeps' share of their roofline: the least time the chip
needs for the operations and bytes of every KrK-Picard sweep of the
window (``bench/counts.py``: two Theta-statistics passes over the
subsets at their own sizes and two factor eighs per sweep) over the
device's busy time, at the ``bench/peaks.py`` row of the device. By that
count the sweep is bound by bytes, the subsets' indices and the factors
that the per-subset gathers read (at N = 100 x 100 the least time is
about a microsecond a sweep); on the chip the gathers run far below
either bound (``PERF.md`` §5). Nothing when the window ran no sweep."""

from bench import counts, peaks


def read(r):
    w = r.work
    if not w.get("sweeps"):
        return None
    sizes = w["subset_sizes"]
    flops = w["sweeps"] * counts.sweep_flops(w["factor_sizes"], sizes)
    nbytes = w["sweeps"] * counts.sweep_bytes(w["factor_sizes"], sizes)
    share, _ = counts.roofline_share(flops, nbytes, r.trace.busy_s,
                                     peaks.peaks(r.device_kind))
    return 100.0 * share
