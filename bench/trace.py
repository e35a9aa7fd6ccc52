"""Reduce a profiler trace of one measured window to device metrics.

Every cell shares this code. The profiler writes an ``.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Each TPU is a plane named
``/device:TPU:<n>``, and its ``XLA Ops`` line holds one event per
executed HLO operation (a Pallas kernel appears as one event named after
its kernel). Host threads are planes whose name starts with ``/host``.

busy_s          union of the op intervals of a device, averaged over the
                devices the cell uses
kernel_s(name)  summed duration of the ops whose name contains ``name``,
                averaged over the devices; raises when no op carries the
                name, so a renamed kernel fails loudly instead of reading 0
collective_s    per device, the part of the collective ops' intervals
                (all-reduce, all-gather, reduce-scatter, all-to-all,
                collective-permute) in which no other op runs; averaged
device_ops      the ops that took most device time, [[name, seconds]]
idle_gaps       device idle time within the window grouped by the
                innermost host event open at the gap's midpoint (the
                benchmark's ``bench.*`` annotations, the runtime's own
                host events), [[label, seconds]]
"""

from __future__ import annotations

import collections
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]            # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather|reducescatter|alltoall|collectivepermute",
    re.IGNORECASE)


class KernelMissing(LookupError):
    """The trace holds no op of the named kernel."""


def union_ns(events: Sequence[Event]) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _merge(events: Sequence[Event]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _minus(spans: List[Tuple[int, int]], cover: List[Tuple[int, int]]
           ) -> int:
    """Length of ``spans`` not covered by ``cover`` (both merged)."""
    total, j = 0, 0
    for s, e in spans:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while cur < e and k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


class DeviceTrace:
    """Ops per device and host annotations of one traced window."""

    def __init__(self, ops: Dict[int, List[Event]], window_s: float,
                 host: Sequence[Event] = ()):
        self.ops = {d: list(v) for d, v in ops.items()}
        self.window_s = float(window_s)
        self.host = sorted(host, key=lambda h: h[1])

    @classmethod
    def from_dir(cls, log_dir: str, window_s: float,
                 devices: Optional[Sequence[int]] = None) -> "DeviceTrace":
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        ops: Dict[int, List[Event]] = {}
        host: List[Event] = []
        for plane in ProfileData.from_file(paths[0]).planes:
            m = _DEVICE.match(plane.name)
            if m:
                dev = int(m.group(1))
                if devices is not None and dev not in devices:
                    continue
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.setdefault(dev, []).extend(
                            (e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events)
            elif plane.name.startswith("/host"):
                for line in plane.lines:
                    host.extend((e.name, int(e.start_ns), int(e.end_ns))
                                for e in line.events)
        if not ops:
            raise LookupError("the trace holds no TPU op")
        return cls(ops, window_s, host)

    def _mean(self, per_device: Dict[int, float]) -> float:
        return sum(per_device.values()) / len(per_device)

    @property
    def busy_s(self) -> float:
        return self._mean({d: union_ns(v) / 1e9 for d, v in self.ops.items()})

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, name: str) -> float:
        hits = {d: sum(e - s for n, s, e in v if name in n) / 1e9
                for d, v in self.ops.items()}
        if not any(hits.values()):
            raise KernelMissing(f"no device op named like {name!r} in the "
                                f"trace")
        return self._mean(hits)

    def collective_s(self) -> Optional[float]:
        """None when the window ran no collective."""
        per = {}
        for d, v in self.ops.items():
            coll = [x for x in v if _COLLECTIVE.search(x[0])]
            if coll:
                other = [x for x in v if not _COLLECTIVE.search(x[0])]
                per[d] = _minus(_merge(coll), _merge(other)) / 1e9
        return self._mean(per) if per else None

    def device_ops(self, n: int = 10) -> List[List]:
        """Top ops by device time; an op is named by its HLO instruction
        (the text before `` = ``)."""
        agg: Dict[str, float] = collections.Counter()
        for v in self.ops.values():
            for name, s, e in v:
                agg[name.split(" = ")[0].lstrip("%")] += \
                    (e - s) / 1e9 / len(self.ops)
        return [[k, v] for k, v in agg.most_common(n)]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle time between consecutive busy spans of the first device,
        grouped by the innermost host event open at the gap's midpoint
        (``host.none`` where none is)."""
        dev = min(self.ops)
        spans = _merge(self.ops[dev])
        agg: Dict[str, float] = collections.Counter()
        live: List[Tuple[int, int, str]] = []    # (-start, end, name)
        nxt = 0
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            mid = (e0 + s1) // 2
            while nxt < len(self.host) and self.host[nxt][1] <= mid:
                name, hs, he = self.host[nxt]
                heapq.heappush(live, (-hs, he, name))
                nxt += 1
            # gaps come in time order, so an event that ended before this
            # midpoint covers no later one either
            while live and live[0][1] < mid:
                heapq.heappop(live)
            agg[live[0][2] if live else "host.none"] += (s1 - e0) / 1e9
        return [[k, v] for k, v in agg.most_common(n)]
