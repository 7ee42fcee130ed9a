"""The serving engine's own stages, as the program records them.

Two kinds of record, both written by ``repro.serve.engine``:

- counters in ``engine.counters``, which the driver snapshots whole
  (``counters0``, ``counters1``): ``queue_wait_s`` over ``admissions``,
  ``prefill_s`` over ``first_tokens``, ``host_s`` and ``sync_s`` over
  ``passes``.  The readers below diff them over the window, less the traced
  stretch, as ``bench.readers`` does.  A program without them (an older
  engine) gives None, and the metric is left out.
- host spans ``repro.serve.<stage>`` in the profiler's trace, nested as the
  engine's calls nest (``step`` > ``admit``, ``pass`` > ``sync`` ...), on
  the device planes' clock.  :func:`split_idle` gives each stretch of device
  idle time to the innermost program span over it; time under no program
  span keeps the benchmark's own span (``bench.<what>``) as its label.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace
from bench.readers import _delta

PROGRAM_PREFIX = "repro."
ENGINE_STEP = "repro.serve.step"

Span = Tuple[str, int, int]


# ------------------------------------------------------------- counters

def _engine_ratio(record: Dict, num: str, den: str) -> Optional[float]:
    """1e3 x the window's change in counter ``num`` over that of ``den``;
    None where the engine keeps no ``num`` or ``den`` did not move."""
    if not all(num in record[c]["engine"] for c in ("counters0", "counters1")):
        return None
    d = _delta(record, "engine", den)
    return _delta(record, "engine", num) / d * 1e3 if d else None


def queue_wait_ms(record: Dict) -> Optional[float]:
    """Mean wait from submit (a preempted request: from its requeue) to
    admission, over the admissions in the window."""
    return _engine_ratio(record, "queue_wait_s", "admissions")


def prefill_ms(record: Dict) -> Optional[float]:
    """Mean time from admission to first token, over the first tokens made
    in the window."""
    return _engine_ratio(record, "prefill_s", "first_tokens")


def host_ms_per_pass(record: Dict) -> Optional[float]:
    """Host time inside ``engine.step()`` not spent waiting for a pass's
    tokens, per pass."""
    return _engine_ratio(record, "host_s", "passes")


# ---------------------------------------------------------------- trace

def read_program_spans(path: str) -> List[Span]:
    """The program's host spans (``repro.*``) in an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        out.append((ev.name, int(ev.start_ns),
                                    int(ev.end_ns)))
    return out


def innermost(spans: Sequence[Span]) -> List[Span]:
    """The timeline of the innermost span: ``(name, start, end)`` pieces in
    order, not overlapping, each under the covering span that started
    last (of two starting together, the shorter)."""
    events = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, _, e) in enumerate(spans)])
    active: Dict[int, Tuple[int, int]] = {}
    out: List[Span] = []
    prev = None
    for t, starts, i in events:      # at one time, ends before starts
        if active and t > prev:
            top = max(active, key=lambda k: active[k])
            name = spans[top][0]
            if out and out[-1][0] == name and out[-1][2] == prev:
                out[-1] = (name, out[-1][1], t)
            else:
                out.append((name, prev, t))
        if starts:
            active[i] = (spans[i][1], -spans[i][2])
        else:
            active.pop(i, None)
        prev = t
    return out


def _gaps(ev: trace.Events) -> Tuple[List[Tuple[int, int]], int]:
    """Every chip's idle intervals in the traced window, and the count of
    chips; as ``trace.reduce`` finds them."""
    lo, hi = ev.window()
    out = []
    for ops in ev.devices.values():
        busy = trace.union(trace.clip([(s, e) for _, s, e in ops], lo, hi))
        out.extend(trace.gaps(busy, lo, hi))
    return out, len(ev.devices)


def split_idle(ev: trace.Events, program: Sequence[Span]) -> Optional[Dict]:
    """Device idle time split exactly over the program's spans.

    ``idle_by_program_span``: seconds (averaged over chips, as
    ``trace.reduce`` does) per innermost program span; the idle time under
    no program span goes to the benchmark's span over its midpoint
    (``bench.<what>``, or ``none``).  ``idle_in_engine_s``: the idle time
    inside any ``repro.serve.step``.  None without a traced window or a
    device."""
    if ev.window() is None or not ev.devices:
        return None
    pieces = innermost(program)
    starts = [s for _, s, _ in pieces]
    steps = trace.union([(s, e) for n, s, e in program if n == ENGINE_STEP])
    index = trace.SpanIndex(ev.spans)
    by_span: Dict[str, int] = defaultdict(int)
    in_engine = 0
    gaps, n_dev = _gaps(ev)
    for lo, hi in gaps:
        covered = []
        j = max(0, bisect.bisect_right(starts, lo) - 1)
        while j < len(pieces) and pieces[j][1] < hi:
            name, s, e = pieces[j]
            a, b = max(s, lo), min(e, hi)
            if b > a:
                by_span[name] += b - a
                covered.append((a, b))
            j += 1
        for a, b in trace.subtract([(lo, hi)], covered):
            label = index.at((a + b) // 2)
            by_span[label if label == "none"
                    else trace.SPAN_PREFIX + label] += b - a
        in_engine += (hi - lo) - trace.total(
            trace.subtract([(lo, hi)], steps))
    return {
        "idle_by_program_span": {
            k: v / n_dev / 1e9
            for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])},
        "idle_in_engine_s": in_engine / n_dev / 1e9,
    }


# ------------------------------------------------------------------ HLO

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
                "c128": 16}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")


def output_bytes(hlo: str) -> Optional[int]:
    """Bytes of an HLO instruction's result, from its text
    (``%copy.3 = bf16[10,512]{1,0} copy(...)``: 10240); the elements of a
    tuple result add up.  None where the text holds no result shape."""
    if " = " not in hlo:
        return None
    rhs = hlo.split(" = ", 1)[1]
    # the result shape ends where the opcode's operand list starts
    head = rhs.split("(", 1)[0] if not rhs.startswith("(") else \
        rhs[:_close(rhs)]
    n, found = 0, False
    for dtype, dims in _SHAPE.findall(head):
        if dtype not in _DTYPE_BYTES:
            continue
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        n += size * _DTYPE_BYTES[dtype]
        found = True
    return n if found else None


def _close(text: str) -> int:
    """Index just past the parenthesis that closes ``text[0]``."""
    depth = 0
    for i, ch in enumerate(text):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return i + 1
    return len(text)
