"""Device time per phase of the program, from the names XLA carries.

The program traces each phase of the robust step under a
``jax.named_scope("robust.<phase>")`` (``repro.obs.scope``), and XLA keeps
the name in every instruction's ``op_name`` metadata.  A device event of
a trace (``trace_reduce.Trace``) names an HLO instruction
(``fusion.5 = f32[99580800] fusion``) but not the program it belongs to,
and instruction names are unique only inside one program: the train
window also runs the batch program, whose ``fusion.N`` are not the
step's.  So each event is matched to the programs loaded in the process
by its whole label (name, result shape, opcode); where several programs
hold the same label, to the program of the events around it, since one
program's execution is a contiguous run of events on the chip.

Each instant of busy time goes to the innermost device event covering it
(a loop's event spans its body's, so the loop counts only where no body
op runs), and from there to that event's phase, to :data:`UNSCOPED` (an
instruction of a scoped program outside every scope) or to
:data:`OTHER` (an instruction of another program, or of none loaded).
The parts sum to the busy time.
"""
from __future__ import annotations

import bisect
import heapq
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as T

SCOPE = re.compile(r"robust\.(\w+)")
UNSCOPED = "unscoped"
OTHER = "other programs"
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Label (as :func:`trace_reduce.op_label` writes a device event's
    name) -> ``op_name`` of every instruction in one compiled module's
    text; ``""`` where the instruction has none."""
    out = {}
    for line in hlo_text.splitlines():
        if not _INSTR.match(line):
            continue
        on = _OP_NAME.search(line)
        text = re.sub(r"^\s*ROOT\s+", "", line).strip()
        out[T.op_label(text)] = on.group(1) if on else ""
    return out


def phase(op_name: str) -> Optional[str]:
    """The innermost ``robust.<phase>`` of an ``op_name``, or None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def is_scoped(program: Dict[str, str]) -> bool:
    return any(SCOPE.search(on) for on in program.values())


def live_programs(jax) -> List[Tuple[str, Dict[str, str]]]:
    """``(module name, label -> op_name)`` of every executable loaded on
    the first device's client, read from the text ``Compiled.as_text()``
    gives."""
    return [(m.name, hlo_op_names(m.to_string()))
            for exe in jax.devices()[0].client.live_executables()
            for m in exe.hlo_modules()]


def self_ns(events: Sequence[T.Event], lo: float, hi: float) -> List[float]:
    """Each event's self time inside ``[lo, hi)``: the instants at which it
    is the innermost event covering them, i.e. the latest to start (the
    shorter, then the later listed, where two start together)."""
    spans = sorted((max(e.start_ns, lo), min(e.end_ns, hi), i)
                   for i, e in enumerate(events)
                   if e.end_ns > lo and e.start_ns < hi)
    cuts = sorted({t for a, b, _ in spans for t in (a, b)})
    out = [0.0] * len(events)
    heap: List[tuple] = []
    k = 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= t0:
            a, b, i = spans[k]
            heapq.heappush(heap, (-a, b - a, -i, b))
            k += 1
        while heap and heap[0][3] <= t0:
            heapq.heappop(heap)
        if heap:
            out[-heap[0][2]] += t1 - t0
    return out


def event_programs(events: Sequence[T.Event],
                   programs: Sequence[Dict[str, str]]
                   ) -> List[Optional[int]]:
    """The index of the program each event belongs to: the one program
    holding the event's label; where several do, the one among them with
    an event of a label no other program holds nearest in time (one
    program's execution is a contiguous run on the chip, and programs are
    dispatched one after another); None where no program holds it."""
    holders: Dict[str, List[int]] = {}
    for j, prog in enumerate(programs):
        for label in prog:
            holders.setdefault(label, []).append(j)
    out: List[Optional[int]] = [None] * len(events)
    anchors: Dict[int, List[Tuple[float, float]]] = {}
    for i, e in enumerate(events):
        c = holders.get(e.name, [])
        if len(c) == 1:
            out[i] = c[0]
            anchors.setdefault(c[0], []).append((e.start_ns, e.end_ns))
    for spans in anchors.values():
        spans.sort()
    starts = {j: [a for a, _ in spans] for j, spans in anchors.items()}

    def gap(j: int, e: T.Event) -> float:
        spans, at = anchors.get(j, []), starts.get(j, [])
        k = bisect.bisect_right(at, e.start_ns)
        before = e.start_ns - spans[k - 1][1] if k else math.inf
        after = spans[k][0] - e.end_ns if k < len(spans) else math.inf
        return max(min(before, after), 0.0)

    for i, e in enumerate(events):
        c = holders.get(e.name, [])
        if len(c) > 1:
            out[i] = min(c, key=lambda j: (gap(j, e), j))
    return out


def attribute(trace: T.Trace, dev: int,
              programs: Sequence[Dict[str, str]]) -> List[tuple]:
    """``(label, op_name, part, self ns)`` of every device event with self
    time inside the window; ``part`` is its phase, :data:`UNSCOPED` or
    :data:`OTHER`."""
    events = trace.devices.get(dev, [])
    scoped = [is_scoped(p) for p in programs]
    out = []
    for e, ns, j in zip(events, self_ns(events, *trace.window),
                        event_programs(events, programs)):
        if ns <= 0:
            continue
        if j is None or not scoped[j]:
            out.append((e.name, "", OTHER, ns))
        else:
            on = programs[j][e.name]
            out.append((e.name, on, phase(on) or UNSCOPED, ns))
    return out


def phase_ns(parts: Sequence[tuple]) -> Dict[str, float]:
    """Busy time by phase, :data:`UNSCOPED` and :data:`OTHER`, from
    :func:`attribute`; the parts sum to ``trace_reduce.busy_ns``."""
    out: Dict[str, float] = {}
    for _, _, part, ns in parts:
        out[part] = out.get(part, 0.0) + ns
    return out


def unscoped_ops(parts: Sequence[tuple], k: int = 10) -> List[list]:
    """The ``k`` instructions of scoped programs with the most self time
    outside every scope, as ``[label, op_name, seconds]``."""
    acc: Dict[Tuple[str, str], float] = {}
    for label, on, part, ns in parts:
        if part == UNSCOPED:
            acc[(label, on)] = acc.get((label, on), 0.0) + ns
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[label, on, ns * 1e-9] for (label, on), ns in best]


def kernel_name(instruction: str) -> str:
    """``fused_select.3`` -> ``fused_select``: a Pallas call's instruction
    is named after its kernel."""
    return re.sub(r"\.\d+$", "", instruction)


def kernels_by_name(trace: T.Trace, dev: int,
                    cc_bytes: Dict[str, float]) -> Dict[str, list]:
    """For each kernel name, ``[instructions, bytes, ns]`` of its calls'
    executions inside the window, counted as
    ``trace_reduce.kernel_bytes_and_ns`` counts them."""
    out: Dict[str, list] = {}
    for instr in cc_bytes:
        out.setdefault(kernel_name(instr), [0, 0.0, 0.0])[0] += 1
    lo, hi = trace.window
    for e in trace.devices.get(dev, ()):
        instr = T.instruction(e.name)
        if instr in cc_bytes and e.start_ns >= lo and e.end_ns <= hi:
            row = out[kernel_name(instr)]
            row[1] += cc_bytes[instr]
            row[2] += e.dur_ns
    return out
