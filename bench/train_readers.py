"""The arithmetic behind the training cell's metrics, on the training
driver's run record (``bench.drivers.train``).

Steps are ``(start, end, tokens, finite)`` in seconds from the window's
start, which is the call of its first step; the window ends at the last
one's return (``window_s``).  ``serve_tok_s`` reads the record's
``requests``, one a step (``bench.readers.serve_tokens_per_s``).  In a
traced run the profiler slows the device while it records, so the
metrics read from the host clock or the trainer's counters leave out the
traced stretch (``traced``).
"""

from __future__ import annotations

from typing import Dict, Optional

from bench.readers import _delta
from bench.stats import share

GROUP = "trainer"


def host_ms_per_step(record: Dict) -> Optional[float]:
    """Host time in ``Trainer.step()`` not spent blocked on the step's
    metrics, per step, from the trainer's own clock."""
    if GROUP not in record.get("counters0", {}):
        return None
    n = _delta(record, GROUP, "steps")
    return _delta(record, GROUP, "host_s") / n * 1e3 if n else None


def step_mfu(record: Dict) -> Optional[float]:
    """The least time one step needs at the chips' peaks
    (``bench.counts.train_step``) over the mean host time of the steps
    clear of the traced stretch."""
    tr = record.get("traced")
    steps = [s for s in record.get("steps", ())
             if tr is None or s[1] <= tr[0] or s[0] >= tr[1]]
    if not steps or "least_step_s" not in record:
        return None
    mean = sum(s[1] - s[0] for s in steps) / len(steps)
    return share(record["least_step_s"], mean)


def collective_share(record: Dict) -> Optional[float]:
    """Collective time with no other operation running on the chip (an
    enclosing loop is not an operation: ``collective_ns``), over the
    traced window, for the chip with the most."""
    tr = record.get("trace")
    if not tr or "collective_ns" not in tr:
        return None
    worst = max(exposed for _, exposed in tr["collective_ns"].values())
    return share(worst / 1e9, tr["window_s"])
