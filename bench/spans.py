"""Means of the program's own spans (``repro.obs.trace.FlightRecorder``)
over the ticks a per-layer reader is given.

A tick record holds the tick's spans, ``(name, t0, t1)`` in seconds,
and the chunk records it popped (``"chunks"``: each chunk's ``spans``
from its way in).  A program that records no such span gives no mean.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional


def _mean_ms(per_item: List[float]) -> Optional[float]:
    return 1e3 * sum(per_item) / len(per_item) if per_item else None


def _seconds(spans, name: str) -> Optional[float]:
    hits = [b - a for n, a, b in spans if n == name]
    return sum(hits) if hits else None


def _each(records: Iterable[Dict], name: str) -> List[float]:
    out = []
    for r in records:
        s = _seconds(r["spans"], name)
        if s is not None:
            out.append(s)
    return out


def tick_ms(ticks: List[Dict], name: str) -> Optional[float]:
    """Mean milliseconds of span ``name`` per stepped tick (a tick with a
    ``dispatch`` span) that recorded it."""
    stepped = [tk for tk in ticks if _seconds(tk["spans"], "dispatch") is not None]
    return _mean_ms(_each(stepped, name))


def chunk_ms(ticks: List[Dict], name: str) -> Optional[float]:
    """Mean milliseconds of chunk span ``name`` per chunk popped by the
    ticks that recorded it."""
    return _mean_ms(_each((c for tk in ticks for c in tk.get("chunks", ())), name))
