"""Span tracing for the superstep pipeline (DESIGN.md §11).

A ``Tracer`` records wall-clock spans around the named phases of a
superstep (ingest → place → migrate → compute → commit, plus the sharded
backend's bucket/dispatch/comm/host-sync children).  Three rules keep the
numbers honest:

* **monotonic clocks** — every timestamp comes from
  ``time.perf_counter_ns`` (never ``time.time``), so NTP adjustments can't
  fold a phase negative;
* **explicit fences** — JAX dispatch is asynchronous, so a span that
  closes without a ``fence`` on the arrays it produced measures *dispatch*
  time, not device time.  ``Span.fence``/``Tracer.fence`` call
  ``jax.block_until_ready`` and are no-ops when tracing is disabled;
* **null object when disabled** — ``NULL_TRACER`` hands out one shared
  no-op span, so the instrumented hot path does no clock reads, no
  allocation and no fencing unless ``SystemConfig.telemetry.trace`` turned
  tracing on (the overhead budget is §11's <3%).

Spans nest: every span event carries its ``id``, its ``parent`` (the span
open around it, None at the root) and its ``depth``, so a reader gets self
time as duration minus children; the Chrome export relies on timestamp
containment (Perfetto renders same-track ``X`` events as a flame graph).
While a span is open it also holds a ``jax.profiler.TraceAnnotation``
named ``xdgp/<span>``, so any ``jax.profiler`` capture shows the program's
phases on the capture's own clock, nested as they ran, beside the device's
ops.

Two kinds of counter come from inside the program: ``host_syncs`` — the
device→host reads made through ``host_read`` since the last
``flush_syncs`` (one sample per superstep, ``adapt()`` or ``converge()``,
the count per call site in its attrs) — and ``compile_s`` — each backend
compile or persistent-cache load JAX reports while the tracer lives
(``stage`` in its attrs).

Two exports share one in-memory event list:

* ``write_jsonl(path)``  — one JSON object per line; first line is a
  ``meta`` header (schema version, clock, run manifest).  This is the file
  ``python -m repro.obs.report`` summarises and ``repro.obs.schema``
  validates.
* ``write_chrome(path)`` — Chrome ``trace_event`` JSON for
  chrome://tracing / Perfetto (``ui.perfetto.dev``).
"""
from __future__ import annotations

import json
import os
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple, TypeVar

import jax

T = TypeVar("T")

TRACE_SCHEMA_VERSION = 1


class _NullSpan:
    """The shared do-nothing span ``NULL_TRACER`` hands out."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass

    def fence(self, *arrays: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracing disabled: every hook is a constant-time no-op.

    The session always holds *a* tracer, so the instrumented code never
    branches on "is tracing on?" — the null object absorbs the calls.
    """

    enabled = False
    events: tuple = ()

    def span(self, name: str, **attrs: Any) -> _NullSpan:
        return _NULL_SPAN

    def fence(self, *arrays: Any) -> None:
        pass

    def counter(self, name: str, value: float, **attrs: Any) -> None:
        pass

    def add_span(self, name: str, duration_s: float, **attrs: Any) -> None:
        pass

    def host_read(self, conv: Callable[[Any], T], value: Any,
                  site: str) -> T:
        return conv(value)

    def flush_syncs(self) -> None:
        pass

    def __repr__(self) -> str:
        return "<NullTracer (tracing disabled)>"


NULL_TRACER = NullTracer()


class Span:
    """One live span: created by ``Tracer.span``, used as a context manager."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_id", "_parent",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._t0 = 0
        self._id = 0
        self._parent: Optional[int] = None
        self._annotation: Any = None

    def set(self, **attrs: Any) -> None:
        """Attach attributes to the span (recorded at exit)."""
        self.attrs.update(attrs)

    def fence(self, *arrays: Any) -> None:
        """``jax.block_until_ready`` on the span's products, so async
        dispatch cannot move their device time out of this span."""
        for a in arrays:
            jax.block_until_ready(a)

    def __enter__(self) -> "Span":
        tr = self._tracer
        self._id, self._parent = tr._new_span_id()
        tr._open.append(self._id)
        self._annotation = jax.profiler.TraceAnnotation("xdgp/" + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        tr = self._tracer
        tr._open.pop()
        tr._emit(self.name, self._t0, t1 - self._t0, self.attrs, self._id,
                 self._parent)
        return False


class Tracer:
    """Collects span/counter events in memory; exports JSONL + Chrome."""

    enabled = True

    def __init__(self, *, meta: Optional[Dict[str, Any]] = None):
        self._origin = time.perf_counter_ns()
        self._open: List[int] = []      # ids of the open spans, innermost last
        self._next_id = 0
        self.syncs: Dict[str, int] = {}  # reads since flush_syncs, by site
        self.meta: Dict[str, Any] = dict(meta or {})
        self.events: List[Dict[str, Any]] = []
        _watch_compiles(self)

    # -- recording ----------------------------------------------------------
    def span(self, name: str, **attrs: Any) -> Span:
        """A context-manager span; ``attrs`` land in the record at exit."""
        return Span(self, name, attrs)

    def fence(self, *arrays: Any) -> None:
        """Standalone fence (outside any span): block until ready."""
        for a in arrays:
            jax.block_until_ready(a)

    def _new_span_id(self) -> Tuple[int, Optional[int]]:
        """A fresh span id and the id of the span open around it (None at
        the root)."""
        sid = self._next_id
        self._next_id += 1
        return sid, (self._open[-1] if self._open else None)

    def _emit(self, name: str, t0_ns: int, dur_ns: int,
              attrs: Dict[str, Any], sid: int, parent: Optional[int]) -> None:
        ev: Dict[str, Any] = {
            "type": "span", "name": name,
            "ts_us": (t0_ns - self._origin) / 1000.0,
            "dur_us": dur_ns / 1000.0,
            "depth": len(self._open),
            "id": sid, "parent": parent,
        }
        if attrs:
            ev["attrs"] = attrs
        self.events.append(ev)

    def add_span(self, name: str, duration_s: float, **attrs: Any) -> None:
        """Record a synthetic span ending *now* with a known duration —
        how probe-measured phases (comm decomposition) enter the trace.
        It has no profiler annotation: it did not run as one interval."""
        t1 = time.perf_counter_ns()
        dur_ns = int(duration_s * 1e9)
        self._emit(name, t1 - dur_ns, dur_ns, attrs, *self._new_span_id())

    def host_read(self, conv: Callable[[Any], T], value: Any,
                  site: str) -> T:
        """``conv(value)`` — a blocking device→host read such as
        ``float(x)`` or ``np.asarray(x)`` — counted under ``site``."""
        self.syncs[site] = self.syncs.get(site, 0) + 1
        return conv(value)

    def flush_syncs(self) -> None:
        """Record the ``host_syncs`` counter — the reads since the last
        flush, with the count of each site as an attribute — and restart
        the count."""
        self.counter("host_syncs", sum(self.syncs.values()), **self.syncs)
        self.syncs = {}

    def counter(self, name: str, value: float, **attrs: Any) -> None:
        """Record a counter sample (renders as a counter track in Perfetto)."""
        ev: Dict[str, Any] = {
            "type": "counter", "name": name,
            "ts_us": (time.perf_counter_ns() - self._origin) / 1000.0,
            "value": float(value),
        }
        if attrs:
            ev["attrs"] = attrs
        self.events.append(ev)

    # -- export -------------------------------------------------------------
    def header(self) -> Dict[str, Any]:
        return {"type": "meta", "schema": TRACE_SCHEMA_VERSION,
                "clock": "perf_counter_ns", "unit": "us", **self.meta}

    def write_jsonl(self, path: str) -> str:
        """One event per line, ``meta`` header first (the report/schema
        contract — see ``repro.obs.schema``)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(json.dumps(self.header()) + "\n")
            for ev in self.events:
                f.write(json.dumps(ev, default=float) + "\n")
        return path

    def write_chrome(self, path: str) -> str:
        """Chrome ``trace_event`` export (open in Perfetto / chrome://tracing)."""
        pid = os.getpid()
        out: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": self.meta.get("label", "repro")},
        }]
        for ev in self.events:
            if ev["type"] == "span":
                out.append({"name": ev["name"], "ph": "X", "pid": pid,
                            "tid": 0, "ts": ev["ts_us"], "dur": ev["dur_us"],
                            "args": ev.get("attrs", {})})
            elif ev["type"] == "counter":
                out.append({"name": ev["name"], "ph": "C", "pid": pid,
                            "tid": 0, "ts": ev["ts_us"],
                            "args": {"value": ev["value"]}})
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": out, "displayTimeUnit": "ms"}, f)
        return path

    # -- summaries ----------------------------------------------------------
    def phase_totals(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name totals (count / total / mean seconds) — the same
        aggregation the report CLI prints, available in-process."""
        out: Dict[str, Dict[str, float]] = {}
        for ev in self.events:
            if ev["type"] != "span":
                continue
            row = out.setdefault(ev["name"],
                                 {"count": 0, "total_s": 0.0, "mean_s": 0.0})
            row["count"] += 1
            row["total_s"] += ev["dur_us"] / 1e6
        for row in out.values():
            row["mean_s"] = row["total_s"] / max(row["count"], 1)
        return out

    def __repr__(self) -> str:
        return f"<Tracer events={len(self.events)}>"


# -- compile time from inside the program -----------------------------------
# JAX reports each backend compile (a persistent-cache load included) and
# each cache load through ``jax.monitoring``; one listener, registered with
# the first Tracer, forwards them to every live Tracer as ``compile_s``.
COMPILE_STAGES = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_COMPILE_WATCHERS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_compile_listener_registered = False


def _on_compile_event(event: str, duration_s: float, **_: Any) -> None:
    if not _COMPILE_WATCHERS:
        return
    stage = COMPILE_STAGES.get(event)
    if stage is not None:
        for tracer in list(_COMPILE_WATCHERS):
            tracer.counter("compile_s", duration_s, stage=stage)


def _watch_compiles(tracer: Tracer) -> None:
    global _compile_listener_registered
    if not _compile_listener_registered:
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        _compile_listener_registered = True
    _COMPILE_WATCHERS.add(tracer)
