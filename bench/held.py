"""The part of a traced closed-loop window that its device trace holds.

A k-DPP call at N = 10^4 runs about 130,000 XLA ops: the ESP scan and the
conditional-draw scan run their loop body's ops on each of their 10^4
iterations, and the profiler records every one. The profiler's buffer
fills after about 3 s of a 10 s window, and the device trace ends there:
what follows is missing from the trace, not idle. A reader that sets the
work of the whole window against the trace's busy time overstates the
device's speed by the share that is missing; one that reads a ratio of
two device times counts a call that the trace cuts in half.

``held`` gives the calls the trace holds whole, those whose ``bench.block``
span (the closed loop's wait for a call's result) ends by the trace's
last op, and the busy time of the first device up to the end of the last
of them. Nothing when the trace holds no ``bench.block`` span or no call
whole.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from bench.trace import Event, union_ns


def held(r) -> Optional[Tuple[int, List[Event], float]]:
    """(calls held whole, the first device's ops up to the end of the last
    of them, their busy seconds)."""
    ops = r.trace.ops[min(r.trace.ops)]
    last = max(e for _, _, e in ops)
    ends = [e for n, _, e in r.trace.host if n == "bench.block" and e <= last]
    if not ends:
        return None
    t_end = max(ends)
    kept = [(n, s, min(e, t_end)) for n, s, e in ops if s < t_end]
    return len(ends), kept, union_ns(kept) / 1e9
