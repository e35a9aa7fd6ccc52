"""Device time of the fused phase-2 select kernel (the ``pallas_call``
named after ``phase2_select_pallas``) as a share of device busy time in
the k-DPP cell, over the calls the device trace holds whole
(``bench/held.py``). The rest of busy time is the ESP table and
conditional-draw scans, which one jitted program hides from the trace.
Nothing when the trace holds no call whole or no such kernel."""

from bench.held import held


def read(r):
    got = held(r)
    if got is None:
        return None
    _, ops, busy_s = got
    kernel_ns = sum(e - s for n, s, e in ops if "phase2_select_pallas" in n)
    if not kernel_ns:
        return None
    return 100.0 * kernel_ns / 1e9 / busy_s
