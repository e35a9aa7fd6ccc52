"""Device time of the fused phase-2 select kernel (the ``pallas_call``
named after ``phase2_select_pallas``) as a share of device busy time;
nothing when the window ran no such kernel."""

from bench.trace import KernelMissing


def read(r):
    try:
        return 100.0 * r.trace.kernel_s("phase2_select_pallas") / r.trace.busy_s
    except KernelMissing:
        return None
