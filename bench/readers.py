"""The arithmetic behind each metric, on a driver's run record.

A metric file under ``bench/metrics/`` names one of these functions as its
``read``.  Each returns a number, or None when the record holds nothing to
read (no trace, no request), and the runner then leaves the metric out.

Times in a serving record are seconds from the window's start; the
window is ``[0, window_s)``.  In a traced run the profiler slows the
device while it records, so the per-layer metrics read from the host clock
or the engine's counters leave out the traced stretch (``traced``).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from bench.stats import percentile, share


def _due(record: Dict) -> List[Dict]:
    """The requests submitted, those of the lead-in before the window
    (due before 0) with them, less those the engine refused."""
    return [r for r in record["requests"] if not r["failed"]]


# ---------------------------------------------------------------- serving

def ttft_ms(record: Dict, q: float) -> Optional[float]:
    """Per request due in the window: its due time to its first token's
    delivery; a request with no first token when the window closes counts
    at the close, so that a stall cannot hide.  The ``q``-th percentile."""
    W = record["window_s"]
    vals = [((r["stamps"][0] if r["stamps"] and r["stamps"][0] <= W else W)
             - r["due"]) * 1e3 for r in _due(record) if 0 <= r["due"] < W]
    return percentile(vals, q)


def itl_ms(record: Dict, q: float) -> Optional[float]:
    """Every gap between consecutive output tokens of a request, both
    delivered in the window.  Tokens one ``step()`` delivers together are
    0 apart."""
    W = record["window_s"]
    vals = []
    for r in _due(record):
        st = [t for t in r["stamps"] if 0 <= t <= W]
        vals.extend((b - a) * 1e3 for a, b in zip(st, st[1:]))
    return percentile(vals, q)


def serve_tokens_per_s(record: Dict) -> Optional[float]:
    """Output tokens delivered in the window, plus the prompt tokens of
    the requests whose first token was delivered in it, over the window."""
    W = record["window_s"]
    out = prompt = 0
    for r in _due(record):
        out += sum(1 for t in r["stamps"] if 0 <= t <= W)
        if r["stamps"] and 0 <= r["stamps"][0] <= W:
            prompt += r["prompt_len"]
    return (out + prompt) / W if W else None


def setup_s(record: Dict) -> Optional[float]:
    return record["setup_s"]


def _untraced(record: Dict, t: float) -> bool:
    tr = record.get("traced")
    return tr is None or not tr[0] <= t < tr[1]


def _steps(record: Dict) -> List:
    """Steps (start, end, passes, passes seen, least seconds) clear of the
    traced stretch."""
    tr = record.get("traced")
    return [s for s in record["steps"]
            if tr is None or s[1] <= tr[0] or s[0] >= tr[1]]


def gen_late_p99_ms(record: Dict) -> Optional[float]:
    """How late the generator submitted each request after its due time."""
    vals = [(r["submit"] - r["due"]) * 1e3 for r in record["requests"]
            if r["due"] >= 0 and _untraced(record, r["submit"])]
    return percentile(vals, 99)


def _delta(record: Dict, group: str, key: str) -> float:
    d = record["counters1"][group][key] - record["counters0"][group][key]
    tr = record.get("counters_traced")
    if tr:
        d -= tr[1][group][key] - tr[0][group][key]
    return d


def prefix_reuse_share(record: Dict) -> Optional[float]:
    """Prompt tokens the pool mapped from shared pages over the prompt
    tokens of the requests admitted in the window."""
    W = record["window_s"]
    admitted = sum(r["prompt_len"] for r in _due(record)
                   if r["admitted"] is not None and 0 <= r["admitted"] <= W
                   and _untraced(record, r["admitted"]))
    return share(_delta(record, "pool", "shared_tokens_reused"), admitted)


def lanes_per_pass(record: Dict) -> Optional[float]:
    passes = _delta(record, "engine", "passes")
    return _delta(record, "engine", "lane_tokens") / passes if passes else None


def pass_ms(record: Dict) -> Optional[float]:
    """Host time inside ``engine.step()`` over the passes it ran."""
    steps = _steps(record)
    passes = sum(s[2] for s in steps)
    busy = sum(s[1] - s[0] for s in steps)
    return busy / passes * 1e3 if passes else None


def serve_step_mfu(record: Dict) -> Optional[float]:
    """The least time the chip needs for the window's passes (per pass the
    larger of operations over peak and bytes over bandwidth, from
    ``bench.counts``) over the host time of the steps that ran them."""
    steps = _steps(record)
    return share(sum(s[4] for s in steps), sum(s[1] - s[0] for s in steps))


# ------------------------------------------------------------ from a trace

def idle_share(record: Dict) -> Optional[float]:
    """1 - busy / traced window, for the chip with the most idle time."""
    tr = record.get("trace")
    return 100.0 * tr["idle_share_max"] if tr else None
