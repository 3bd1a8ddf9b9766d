"""Span tracing of the robust step: named scopes, a span ring, host spans.

Three records, one phase vocabulary:

* **Scopes in the compiled program.**  :func:`scope` opens a
  ``jax.named_scope("robust.<name>")`` for one of :data:`SCOPES`.  XLA
  carries the name into every instruction's ``op_name`` metadata (fusions
  take their root op's), so a device profile attributes each operation to
  the workers' forward/backward (``jvp(robust.workers)`` and
  ``transpose(jvp(robust.workers))``), the byzantine rows and the wire,
  stats, plan, apply, or the update.  A named scope adds no jaxpr
  equation: it costs nothing at run time and needs no switch.
* **The span ring.**  XLA has no in-graph wall clock, so device-side
  records are *logical*: each is (seq, round, phase, payload), written
  into a fixed-capacity ring buffer that rides in the scan carry as a
  registered pytree (:class:`TraceState`).  ``seq`` is the monotone record
  counter — it orders records across ring wraparound — ``round`` is the
  optimizer step the span belongs to, ``phase`` indexes :data:`PHASES`,
  and ``payload`` is one phase-specific scalar (selection mass, grad
  norm, plan_reused flag, ...).
* **Host spans.**  :class:`SpanTracer` times what the host does around
  the jitted step and writes the same spans into ``jax.profiler``'s trace
  (``repro:<name>``), on the profiler's clock, so a device profile shows
  them beside the device's operations.

:func:`export_chrome_trace` writes the host spans and the drained ring
records as one Chrome trace-event JSON (https://ui.perfetto.dev or
chrome://tracing): ring records are instant events at the end of their
step's host span, since the ring has no duration to report.  Device
durations come from a ``jax.profiler`` trace, read by the scopes above.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

#: Pipeline phases, in program order.  ``select_plan`` is the async
#: degradation branch (DESIGN.md §13); synchronous trainers record the
#: first three.
PHASES = ("stats", "plan", "apply", "select_plan")
PH_STATS, PH_PLAN, PH_APPLY, PH_SELECT_PLAN = range(len(PHASES))

#: Named scopes of the robust step, in program order: the n workers'
#: forward and backward, the byzantine rows and the codec's wire, the
#: three aggregation phases (as in :data:`PHASES`), the optimizer update
#: with the step's metrics.
SCOPES = ("workers", "attack", "stats", "plan", "apply", "update")
SCOPE_PREFIX = "robust."


def scope(name: str):
    """``jax.named_scope("robust.<name>")`` for one of :data:`SCOPES`.

    Open a fresh one around the code that traces (``with scope(...)``):
    one object shared by concurrent traces would mix their name stacks.
    A phase is never opened inside itself: the name would appear twice in
    the ``op_name``.
    """
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(SCOPE_PREFIX + name)

_COLS = 4  # (seq, round, phase, payload)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("slots", "head"),
    meta_fields=("capacity",))
@dataclasses.dataclass(frozen=True)
class TraceState:
    """Fixed-capacity span ring: ``slots`` is (capacity, 4) float32.

    ``head`` counts records ever written; the live window is the last
    ``min(head, capacity)`` records and ``head % capacity`` is the next
    write position.  Storing seq as float32 keeps the ring a single
    homogeneous array; it is exact up to 2^24 records — far beyond any
    ring's retention window.
    """

    capacity: int
    slots: Array
    head: Array


def init_trace(capacity: int) -> TraceState:
    return TraceState(capacity=int(capacity),
                      slots=jnp.zeros((int(capacity), _COLS), jnp.float32),
                      head=jnp.zeros((), jnp.int32))


def record(trace: Optional[TraceState], phase: int, round_idx,
           payload=0.0) -> Optional[TraceState]:
    """Append one span record (pure ``jnp``; ``None`` passes through).

    ``phase`` is a static int from :data:`PHASES`; ``round_idx`` and
    ``payload`` may be traced scalars.
    """
    if trace is None:
        return trace
    pos = trace.head % trace.capacity
    row = jnp.stack([
        trace.head.astype(jnp.float32),
        jnp.asarray(round_idx, jnp.float32),
        jnp.float32(phase),
        jnp.asarray(payload, jnp.float32),
    ])
    return dataclasses.replace(
        trace,
        slots=trace.slots.at[pos].set(row),
        head=trace.head + 1)


def drain(trace: Optional[TraceState]) -> List[Dict[str, Any]]:
    """Host-side: the live window, oldest first (wraparound-safe).

    Records evicted by ring overwrite are gone — that is the contract:
    the ring bounds carry memory, the drain returns whatever survived,
    in seq order.
    """
    if trace is None:
        return []
    slots = np.asarray(trace.slots)
    head = int(trace.head)
    n = min(head, trace.capacity)
    if n == 0:
        return []
    live = slots[np.argsort(slots[:, 0])] if head > trace.capacity \
        else slots[:n]
    out = []
    for seq, rnd, ph, payload in live:
        out.append({
            "seq": int(seq),
            "round": int(rnd),
            "phase": PHASES[int(ph)],
            "payload": float(payload),
        })
    return out


class SpanTracer:
    """Host-side wall-clock spans (``perf_counter``, microseconds).

    The launch layer brackets what the host does around each jitted step::

        tracer = SpanTracer()
        with tracer.span("step", round=i):
            with tracer.span("dispatch"):
                out = step(params, state, ...)
            with tracer.span("wait"):
                jax.block_until_ready(out)

    Each span is also written into ``jax.profiler``'s trace when one is
    being taken: ``repro:<name>`` (a ``TraceAnnotation``), and a ``step``
    span with a ``round`` as a ``StepTraceAnnotation``, so a device
    profile lines the host's work up with the device's.  The recorded
    spans anchor the ring records in :func:`export_chrome_trace`.
    """

    def __init__(self):
        self.spans: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter()

    @contextmanager
    def span(self, name: str, **args):
        rnd = args.get("round")
        if name == "step" and rnd is not None:
            mark = jax.profiler.StepTraceAnnotation("repro:step",
                                                    step_num=int(rnd))
        else:
            mark = jax.profiler.TraceAnnotation("repro:" + name)
        start = time.perf_counter()
        try:
            with mark:
                yield
        finally:
            end = time.perf_counter()
            self.spans.append({
                "name": name,
                "ts_us": (start - self._t0) * 1e6,
                "dur_us": (end - start) * 1e6,
                "args": {k: _jsonable(v) for k, v in args.items()},
            })


def _jsonable(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return str(v)


def export_chrome_trace(path: str, *,
                        device_records: Sequence[Dict[str, Any]] = (),
                        host_spans: Sequence[Dict[str, Any]] = (),
                        meta: Optional[Dict[str, Any]] = None) -> int:
    """Write a Chrome-trace/Perfetto JSON file; returns the event count.

    Host spans become pid 0 / tid 0 duration events at their measured
    wall-clock offsets.  Ring records become pid 1 instant events, one
    track per phase, at the end of the host ``step`` span whose ``round``
    arg matches theirs (the step has finished its records by then).  The
    ring holds no time, so no duration is reported for it; records whose
    round has no such span are left out and counted in
    ``otherData.unanchored_records``.
    """
    events: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": 0,
         "args": {"name": "host (wall clock)"}},
        {"name": "process_name", "ph": "M", "pid": 1,
         "args": {"name": "span ring (records at their step's end)"}},
    ]
    ends = {}
    for s in host_spans:
        events.append({
            "name": s["name"], "ph": "X", "pid": 0, "tid": 0,
            "ts": round(float(s["ts_us"]), 3),
            "dur": round(float(s["dur_us"]), 3),
            "cat": "host", "args": dict(s.get("args", {})),
        })
        rnd = s.get("args", {}).get("round")
        if s["name"] == "step" and rnd is not None:
            ends[int(rnd)] = float(s["ts_us"]) + float(s["dur_us"])

    unanchored = 0
    for r in sorted(device_records, key=lambda r: r["seq"]):
        ts = ends.get(int(r["round"]))
        if ts is None:
            unanchored += 1
            continue
        events.append({
            "name": r["phase"], "ph": "i", "s": "t", "pid": 1,
            "tid": PHASES.index(r["phase"]), "ts": round(ts, 3),
            "cat": "ring",
            "args": {"seq": r["seq"], "round": r["round"],
                     "payload": r["payload"]},
        })
    for tid, phase in enumerate(PHASES):
        events.append({"name": "thread_name", "ph": "M", "pid": 1,
                       "tid": tid, "args": {"name": phase}})

    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "exporter": "repro.obs.trace",
            "note": ("ring records are instants at the end of their "
                     "step's host span; device durations are in a "
                     "jax.profiler trace, under the robust.* scopes"),
            "unanchored_records": unanchored,
            **(meta or {}),
        },
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return len(events)
