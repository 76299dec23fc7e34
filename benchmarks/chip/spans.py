"""The program's own spans in a ``jax.profiler`` capture.

While the session traces (``telemetry.trace``), each span of its tracer
(``repro.obs.trace``) holds a ``TraceAnnotation`` named ``xdgp/<span>``, so
a capture carries the program's phases on its own clock, beside the
device's ops. ``load`` reads them back as the dicts ``xplane.reduce`` takes
in ``spans`` (``name``, ``start_ns``, ``dur_ns``, ``depth``, and the host
thread's line as ``track``), the depth taken from containment on each
line. ``reduce`` then names every idle gap by the innermost program span
running at its midpoint.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List

PREFIX = "xdgp/"


def load(logdir: str) -> List[Dict]:
    """The capture's ``xdgp/`` host events, each with its depth."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    out: List[Dict] = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                out += nest([{"track": line.name, "name": ev.name,
                              "start_ns": float(ev.start_ns),
                              "dur_ns": float(ev.duration_ns)}
                             for ev in line.events
                             if ev.name.startswith(PREFIX)])
    return out


def nest(events: List[Dict]) -> List[Dict]:
    """``events`` of one line, in start order, each given the number of
    events that contain it as ``depth``."""
    events = sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    ends: List[float] = []                 # ends of the enclosing events
    for ev in events:
        while ends and ends[-1] <= ev["start_ns"]:
            ends.pop()
        ev["depth"] = len(ends)
        ends.append(ev["start_ns"] + ev["dur_ns"])
    return events
