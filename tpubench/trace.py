"""Reduction of a profiler trace to the numbers per-layer metrics read.

A trace is reduced to plain interval lists (nanoseconds on the trace's
own clock), which is all the arithmetic below sees:

* ``ops``     -- ``(name, module, start, end)`` of each device operation;
* ``modules`` -- ``(name, start, end)`` of each compiled program run on
  the device;
* ``spans``   -- ``(name, start, end)`` of the benchmark's own host
  spans (``tpubench.*``);
* ``window``  -- ``(start, end)`` of the traced window.

Busy time is the union of the operations' intervals inside the window,
the idle share is one minus busy over the window, and each idle gap is
named by the benchmark span that overlaps it most.  With several chips
busy time is averaged over them.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "tpubench."
WINDOW = SPAN_PREFIX + "window"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    ops: list[tuple[str, str, int, int]] = field(default_factory=list)
    modules: list[tuple[str, int, int]] = field(default_factory=list)
    spans: list[tuple[str, int, int]] = field(default_factory=list)
    window: tuple[int, int] = (0, 0)
    n_devices: int = 1


def union_length(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The gaps inside ``[lo, hi]`` that no interval covers."""
    gaps, cursor = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    return gaps


def busy_ns(tr: Trace) -> float:
    """Device busy time in the window, averaged over the devices."""
    by_dev: dict[str, list] = {}
    for name, module, s, e in tr.ops:
        by_dev.setdefault(module.split("|", 1)[0], []).append((s, e))
    if not by_dev:
        return 0.0
    lo, hi = tr.window
    return sum(union_length(v, lo, hi) for v in by_dev.values()) / max(
        tr.n_devices, 1)


def name_gap(tr: Trace, s: int, e: int) -> str:
    """The benchmark span that overlaps ``[s, e]`` most, else a note."""
    best, best_len = None, 0
    for name, a, b in tr.spans:
        if name == WINDOW:
            continue
        ov = min(b, e) - max(a, s)
        if ov > best_len or (ov == best_len and ov > 0 and best is not None
                             and b - a < best[1]):
            best, best_len = (name, b - a), ov
    return best[0] if best else "outside benchmark spans"


def module_seconds(tr: Trace, pattern: str) -> float:
    """Summed device seconds of the programs whose name matches
    ``pattern`` (a regular expression), inside the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    return sum(max(0, min(e, hi) - max(s, lo))
               for name, s, e in tr.modules if rx.search(name)) / 1e9


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by what the host was doing."""
    per_op: dict[str, float] = {}
    lo, hi = tr.window
    for name, module, s, e in tr.ops:
        key = f"{_MODULE_ID.sub('', module.split('|', 1)[-1])}/{name}"
        per_op[key] = per_op.get(key, 0.0) + max(0, min(e, hi) - max(s, lo))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    first_dev = None
    ivs = []
    for name, module, s, e in tr.ops:
        dev = module.split("|", 1)[0]
        first_dev = dev if first_dev is None else first_dev
        if dev == first_dev:
            ivs.append((s, e))
    gaps = sorted(idle_gaps(ivs, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[name_gap(tr, s, e), (e - s) / 1e9]
                          for s, e in gaps]}


# -- loading a profiler dump ---------------------------------------------------


def load(trace_dir: str, window_span: str = WINDOW) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``.  Device
    operations come from each TPU plane's ``XLA Ops`` line, programs from
    its ``XLA Modules`` line; the benchmark's spans from the host planes.
    The window is the span named ``window_span``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    tr = Trace()
    devs = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devs += 1
            mods = []
            if "XLA Modules" in lines:
                for ev in lines["XLA Modules"].events:
                    mods.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
            mods.sort(key=lambda m: m[1])
            tr.modules.extend(mods)
            starts = [m[1] for m in mods]
            for ev in lines["XLA Ops"].events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][0] if i >= 0 and mods[i][2] >= s else ""
                tr.ops.append((ev.name, f"{plane.name}|{mod}", s, e))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append((ev.name, int(ev.start_ns),
                                         int(ev.end_ns)))
    tr.n_devices = max(devs, 1)
    wins = [(s, e) for name, s, e in tr.spans if name == window_span]
    if not wins:
        raise ValueError(f"no {window_span!r} span in the trace")
    tr.window = wins[-1]
    return tr
