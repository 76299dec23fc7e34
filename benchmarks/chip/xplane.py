"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` capture wrote and keeps
two kinds of events, as plain dicts (``kind``, ``track``, ``name``,
``start_ns``, ``dur_ns``):

* ``op``: operations on a device plane's op line (``XLA Ops``), one track
  per chip;
* ``host``: the benchmark's own ``TraceAnnotation`` spans (``bench/...``).

``reduce`` then works on that list alone, so it is checked on a small
recorded list (``tests/fixtures``):

* busy: the union of op intervals inside the window, per chip, averaged
  over the chips the cell ran on (a chip with no op in the window is
  wholly idle);
* ops: total device time per op (named by ``op_name``), of the ops whose
  name contains a kernel's pattern, and of the collective ops (per chip,
  averaged like busy);
* idle gaps: the stretches of the window in which a chip ran nothing,
  each named by the innermost host span (program span or annotation)
  running at its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OP_LINES = ("XLA Ops",)
ANNOTATION_PREFIX = "bench/"
# the opcodes of HLO ops that move data between chips (an async pair's
# ``-start`` and ``-done`` halves included), as they follow the op's shape
COLLECTIVE = re.compile(r" (all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?\(")


def load(logdir: str, lines: Optional[Dict[str, int]] = None
         ) -> List[Dict]:
    """The capture's op and annotation events; ``lines``, where given, is
    filled with the event count of every device plane's line."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    events: List[Dict] = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                if device and lines is not None:
                    key = f"{plane.name}:{line.name}"
                    lines[key] = lines.get(key, 0) + sum(1 for _ in line.events)
                if device and line.name not in OP_LINES:
                    continue
                for ev in line.events:
                    if device:
                        events.append({"kind": "op", "track": plane.name,
                                       "name": op_name(ev.name),
                                       "collective": is_collective(ev.name),
                                       "start_ns": float(ev.start_ns),
                                       "dur_ns": float(ev.duration_ns)})
                    elif ev.name.startswith(ANNOTATION_PREFIX):
                        events.append({"kind": "host", "track": line.name,
                                       "name": ev.name,
                                       "start_ns": float(ev.start_ns),
                                       "dur_ns": float(ev.duration_ns)})
    return events


def annotated(events: Sequence[Dict]) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) from the first benchmark annotation's start to the
    last one's end; None where the capture holds none."""
    marks = [e for e in events if e["kind"] == "host"]
    if not marks:
        return None
    return (min(e["start_ns"] for e in marks),
            max(e["start_ns"] + e["dur_ns"] for e in marks))


def op_name(hlo: str) -> str:
    """``%fusion.80 = s32[2097153]{0:T(1024)} fusion(...)`` → ``fusion.80
    s32[2097153]``: the op and the shape it makes, not its operands."""
    name, _, rest = hlo.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}".strip()


def _merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def is_collective(hlo: str) -> bool:
    """Whether the op, as the trace writes it (``%all-gather.8 = s32[...]
    all-gather(...)``), moves data between chips: read from its opcode, not
    its name, which JAX may take from the primitive (``%psum_invariant.24
    = s32[4]{0:T(128)} all-reduce(...)``)."""
    return COLLECTIVE.search(hlo) is not None


def reduce(events: Sequence[Dict], window: Tuple[float, float], *,
           kernel: Optional[str] = None, spans: Sequence[Dict] = (),
           chips: int = 1) -> Dict:
    """Device numbers of ``window`` (start_ns, end_ns on the trace's clock).

    ``spans`` are extra host spans already on that clock (dicts with
    ``name``, ``start_ns``, ``dur_ns``, ``depth``) — the program's own
    phases — that name the idle gaps alongside the annotations. ``chips``
    is the number of chips the cell ran on: busy, idle and collective time
    are averaged over them, or over the tracks that ran an op where those
    are more.
    """
    lo, hi = window
    by_track: Dict[str, List[Tuple[float, float]]] = {}
    op_time: Dict[str, float] = {}
    kernel_ns = collective_ns = 0.0
    kernel_count = collective_count = 0
    for ev in events:
        if ev["kind"] != "op":
            continue
        iv = _clip(ev["start_ns"], ev["start_ns"] + ev["dur_ns"], lo, hi)
        if iv is None:
            continue
        by_track.setdefault(ev["track"], []).append(iv)
        op_time[ev["name"]] = op_time.get(ev["name"], 0.0) + iv[1] - iv[0]
        if kernel and kernel in ev["name"]:
            kernel_ns += iv[1] - iv[0]
            kernel_count += 1
        if ev.get("collective"):
            collective_ns += iv[1] - iv[0]
            collective_count += 1
    busy = {t: _merge(iv) for t, iv in by_track.items()}
    # a chip that ran nothing in the window is one gap the whole window long
    idle_chips = max(chips - len(busy), 0)
    per_chip = list(busy.values()) + [[]] * idle_chips
    n = max(len(per_chip), 1)
    busy_ns = sum(b - a for merged in per_chip for a, b in merged)
    host = [dict(ev, depth=-1) for ev in events if ev["kind"] == "host"]
    host += [dict(s) for s in spans]
    gaps: Dict[str, float] = {}
    for merged in per_chip:
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                name = _cover(host, a, b)
                gaps[name] = gaps.get(name, 0.0) + (b - a) / n
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9 if busy else 0.0,
        "chips": len(per_chip),
        "kernel_s": kernel_ns / 1e9,
        "kernel_events": kernel_count,
        "collective_s": collective_ns / n / 1e9,
        "collective_events": collective_count,
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
        "idle_gaps": [[name, ns / 1e9] for name, ns in top_gaps],
    }


def _cover(host: Sequence[Dict], a: float, b: float) -> str:
    """Name of the deepest host span running at the gap's midpoint."""
    mid = (a + b) / 2
    best, depth = "untracked", None
    for s in host:
        if s["start_ns"] <= mid <= s["start_ns"] + s["dur_ns"] and \
                (depth is None or s["depth"] > depth):
            best, depth = s["name"], s["depth"]
    return best
