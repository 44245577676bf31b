"""From a profiler trace to intervals, and from intervals to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three things: each chip's device operations (the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane), the harness's own host spans (events named
``bench/...``), and the traced window (the ``bench/window`` span).  All
are ``(name, start_ns, end_ns)`` on the profiler's one clock.

The reductions below work on those tuples only, so the tests can feed them
a synthetic trace.
"""
from __future__ import annotations

import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"


def _event_text(ev) -> str:
    """The event's name and its string-valued stats, for kernel matching."""
    parts = [ev.name]
    try:
        for _, v in ev.stats:
            if isinstance(v, str):
                parts.append(v)
    except (TypeError, ValueError):
        pass
    return " ".join(parts)


def load(profile_dir: str) -> dict:
    """Device ops per chip, harness spans and window from one trace."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device: dict[int, list] = {}
    texts: dict[int, list] = {}
    spans: list = []
    layout: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        layout.append((plane.name, [ln.name for ln in lines]))
        if m:
            chip = int(m.group(1))
            for ln in lines:
                if ln.name != _OPS_LINE:
                    continue
                evs = [(e.name, e.start_ns, e.end_ns) for e in ln.events]
                device.setdefault(chip, []).extend(evs)
                texts.setdefault(chip, []).extend(
                    _event_text(e) for e in ln.events)
        else:
            for ln in lines:
                spans.extend((e.name, e.start_ns, e.end_ns) for e in ln.events
                             if e.name.startswith(SPAN_PREFIX))
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    return {"device": device, "texts": texts,
            "spans": [s for s in spans if s[0] != WINDOW_SPAN],
            "window": (window[0][1], window[0][2]) if window else None,
            "layout": layout}


def clip(events, lo: float, hi: float) -> list:
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def merge(events) -> list:
    """Union of the events' intervals as sorted, disjoint ``(start, end)``."""
    out: list = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` during which some operation ran."""
    return float(sum(e - s for s, e in merge(clip(events, lo, hi))))


def gaps(events, lo: float, hi: float) -> list:
    """Idle intervals in ``[lo, hi]``: the window less the busy union."""
    out, t = [], lo
    for s, e in merge(clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covering_span(spans, t: float) -> str:
    """The innermost (shortest) harness span that covers time ``t``."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "outside any bench span"


def matching(events, texts, pattern: str) -> list:
    """Events whose name or string stats contain ``pattern``."""
    return [ev for ev, txt in zip(events, texts) if pattern in txt]


def top_ops(device: dict, k: int = 10) -> list:
    """``[[name, seconds], ...]``: the k ops with most device time, summed
    over chips."""
    tot: dict = {}
    for evs in device.values():
        for n, s, e in evs:
            tot[n] = tot.get(n, 0.0) + (e - s) * 1e-9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def longest_gaps(device: dict, spans, lo: float, hi: float,
                 k: int = 10) -> list:
    """``[[span, seconds], ...]``: the k longest idle gaps over all chips,
    each under the harness span covering its midpoint."""
    all_gaps = [g for evs in device.values() for g in gaps(evs, lo, hi)]
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return [[covering_span(spans, (s + e) / 2), (e - s) * 1e-9]
            for s, e in all_gaps[:k]]
