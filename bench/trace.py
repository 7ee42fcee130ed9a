"""Reduction of a profiler trace to device busy time, collective time and
the ``breakdown``.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes
are named ``/device:TPU:<n>``; the operations that ran are the events of
the plane's ``XLA Ops`` line.  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.<what>``) are events on the
host plane, on the same clock.  The traced window is the span
``bench.trace_window`` that the driver opens right after the profiler
starts and closes right before it stops.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter|collectivepermute|alltoall",
    re.IGNORECASE)


def op_name(hlo: str) -> str:
    """An operation's name without its HLO text and instance number:
    ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def find_xplane(root: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union of ``a`` not covered by the union of ``b``."""
    a, b = union(a), union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], busy)


class Events:
    """Plain lists pulled from a trace: per device, its op events; the host
    spans of the benchmark; per device and op event, the event's numeric
    statistics whose name holds ``bytes`` (``op_bytes``, aligned with the
    op events; empty where the trace has none)."""

    def __init__(self, devices: Dict[str, List[Tuple[str, int, int]]],
                 spans: List[Tuple[str, int, int]],
                 op_bytes: Optional[Dict[str, List[Dict[str, float]]]] = None):
        self.devices = devices
        self.spans = spans
        self.op_bytes = op_bytes or {}

    @classmethod
    def from_file(cls, path: str) -> "Events":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices: Dict[str, List[Tuple[str, int, int]]] = {}
        op_bytes: Dict[str, List[Dict[str, float]]] = {}
        spans: List[Tuple[str, int, int]] = []
        for plane in pd.planes:
            if re.fullmatch(r"/device:TPU:\d+", plane.name):
                ops, nbytes = [], []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        for ev in line.events:
                            ops.append((op_name(ev.name), int(ev.start_ns),
                                        int(ev.end_ns)))
                            nbytes.append(_byte_stats(ev))
                devices[plane.name] = ops
                op_bytes[plane.name] = nbytes
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.name, int(ev.start_ns),
                                          int(ev.end_ns)))
        return cls(devices, spans, op_bytes)

    def window(self) -> Optional[Interval]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        return (min(s for s, _ in w), max(e for _, e in w)) if w else None


def _byte_stats(ev) -> Dict[str, float]:
    out = {}
    for name, value in ev.stats:
        if "bytes" in name and isinstance(value, (int, float)):
            out[name] = float(value)
    return out


class SpanIndex:
    """The benchmark's host spans (other than the window), which follow
    one another on one thread, for lookups by time."""

    def __init__(self, spans: Sequence[Tuple[str, int, int]]):
        self.spans = sorted((s, e, n) for n, s, e in spans
                            if n != WINDOW_SPAN)
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t: int) -> str:
        """The span covering ``t``, without the ``bench.`` prefix."""
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.spans[i][1] > t:
            return self.spans[i][2][len(SPAN_PREFIX):]
        return "none"


def reduce(ev: Events, top: int = 10) -> Optional[Dict]:
    """Busy and idle time, collective time with no compute running, the
    longest device operations and the longest idle gaps.  None when the
    trace holds no device operation in its window."""
    win = ev.window()
    if win is None or not ev.devices:
        return None
    lo, hi = win
    length = hi - lo
    per_device = {}
    op_time: Dict[str, int] = defaultdict(int)
    op_count: Dict[str, int] = defaultdict(int)
    op_stat: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    all_gaps: List[Tuple[int, int]] = []
    for name, ops in sorted(ev.devices.items()):
        ivs = clip([(s, e) for _, s, e in ops], lo, hi)
        busy = union(ivs)
        coll = clip([(s, e) for n, s, e in ops if COLLECTIVE.search(n)],
                    lo, hi)
        comp = clip([(s, e) for n, s, e in ops if not COLLECTIVE.search(n)],
                    lo, hi)
        exposed = subtract(coll, comp)
        per_device[name] = {"busy_ns": total(busy),
                            "collective_exposed_ns": total(exposed),
                            "idle_share": 1.0 - total(busy) / length}
        nbytes = ev.op_bytes.get(name) or [{}] * len(ops)
        for (n, s, e), b in zip(ops, nbytes):
            c = min(e, hi) - max(s, lo)
            if c > 0:
                op_time[n] += c
                op_count[n] += 1
                for k, v in b.items():
                    op_stat[n][k] += v
        all_gaps.extend(gaps(busy, lo, hi))
    if not any(d["busy_ns"] for d in per_device.values()):
        return None
    n_dev = len(per_device)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    index = SpanIndex(ev.spans)
    idle_by_span: Dict[str, int] = defaultdict(int)
    for s, e in all_gaps:
        idle_by_span[index.at((s + e) // 2)] += e - s
    return {
        "window_s": length / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device.values()) / n_dev / 1e9,
        "devices": per_device,
        "idle_share_max": max(d["idle_share"] for d in per_device.values()),
        "collective_share_mean": sum(d["collective_exposed_ns"]
                                     for d in per_device.values())
        / n_dev / length,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[index.at((s + e) // 2), (e - s) / 1e9]
                      for s, e in longest],
        "idle_by_span": {k: v / n_dev / 1e9 for k, v in
                         sorted(idle_by_span.items(), key=lambda kv: -kv[1])},
        # per op name, over all chips: [events, seconds, {byte stat: sum}]
        "op_stats": {n: [op_count[n], op_time[n] / 1e9, dict(op_stat[n])]
                     for n, _ in sorted(op_time.items(),
                                        key=lambda kv: -kv[1])[:top]},
    }
