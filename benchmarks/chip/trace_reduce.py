"""Reduction of a profiler trace, and of the compiled HLO, to device metrics.

A trace is kept as plain events: the device operations of each chip (the
"XLA Ops" line of its ``/device:TPU:<i>`` plane), the benchmark's own host
spans (``TraceAnnotation`` names that start with ``bench:``), and the
traced window (the ``bench:window`` span).  Every function below works on
that form, so a small recorded trace checks them without a chip.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Event]]
    host: List[Event]
    window: Tuple[float, float]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def to_json(self) -> dict:
        def ev(e):
            return [e.name, e.start_ns, e.dur_ns]
        return {"devices": {str(k): [ev(e) for e in v]
                            for k, v in self.devices.items()},
                "host": [ev(e) for e in self.host],
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({int(k): [Event(*e) for e in v]
                    for k, v in d["devices"].items()},
                   [Event(*e) for e in d["host"]], tuple(d["window"]))


def op_label(text: str) -> str:
    """A device event's name on a TPU is its whole HLO instruction; the
    label keeps the instruction name, result shape and opcode:
    ``fusion.4 = f32[99580800] fusion``."""
    text = re.sub(r"\{[^{}]*\}", "", text.lstrip("%"))
    name, sep, rest = text.partition(" = ")
    m = re.match(r"(\(.*?\)|\S+)\s+([\w\-]+)", rest) if sep else None
    if m is None:
        return text.split("(", 1)[0].strip()
    return f"{name} = {m.group(1)} {m.group(2)}"


def instruction(label: str) -> str:
    """The HLO instruction name of an event label (``fusion.4``)."""
    return label.partition(" = ")[0].strip().lstrip("%")


def load_xplane(path: str) -> Trace:
    """Reads a ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            devices[int(m.group(1))] = [
                Event(op_label(e.name), float(e.start_ns),
                      float(e.duration_ns))
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [Event(e.name, float(e.start_ns), float(e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name.startswith(SPAN_PREFIX)]
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return Trace(devices, host, (spans[0].start_ns, spans[0].end_ns))


# ---------------------------------------------------------------- intervals
def _clip(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    return sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                  if e.end_ns > lo and e.start_ns < hi)


def busy_intervals(trace: Trace, dev: int) -> List[Tuple[float, float]]:
    """The union of the device's operation intervals inside the window,
    as disjoint sorted intervals."""
    out: List[List[float]] = []
    for a, b in _clip(trace.devices.get(dev, ()), *trace.window):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, dev: int) -> float:
    return sum(b - a for a, b in busy_intervals(trace, dev))


def idle_share(trace: Trace, dev: int) -> float:
    """1 - busy / window, in [0, 1]."""
    return 1.0 - busy_ns(trace, dev) / trace.window_ns


def idle_gaps(trace: Trace, dev: int) -> List[Tuple[float, float]]:
    """The idle intervals of the device inside the window."""
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in busy_intervals(trace, dev):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_activity(trace: Trace, t: float) -> str:
    """The innermost benchmark host span, other than the window, that
    covers time ``t``; ``"none"`` where none does."""
    cover = [e for e in trace.host if e.name != WINDOW_SPAN
             and e.start_ns <= t < e.end_ns]
    if not cover:
        return "none"
    return min(cover, key=lambda e: e.dur_ns).name[len(SPAN_PREFIX):]


def longest_gaps(trace: Trace, dev: int, k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps as ``[host activity, seconds]``, the
    activity read at each gap's midpoint."""
    gaps = sorted(idle_gaps(trace, dev), key=lambda g: g[0] - g[1])[:k]
    return [[host_activity(trace, (a + b) / 2), (b - a) * 1e-9]
            for a, b in gaps]


def top_ops(trace: Trace, dev: int, k: int = 10) -> List[list]:
    """The ``k`` operation names with the most device time, as
    ``[name, seconds]``, inside the window."""
    lo, hi = trace.window
    acc: Dict[str, float] = {}
    for e in trace.devices.get(dev, ()):
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            acc[e.name] = acc.get(e.name, 0.0) + (b - a)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


def op_ns(trace: Trace, dev: int, pick) -> Tuple[float, int]:
    """Summed device time inside the window of the operations whose name
    ``pick`` accepts, and how many there were."""
    lo, hi = trace.window
    total, count = 0.0, 0
    for e in trace.devices.get(dev, ()):
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a and pick(e.name):
            total += b - a
            count += 1
    return total, count


def is_collective(name: str) -> bool:
    return any(instruction(name).startswith(c) for c in COLLECTIVES)


def spans_in_window(trace: Trace, name: str) -> int:
    """How many host spans called ``bench:<name>`` end inside the
    window: the steps or rounds the window completed."""
    lo, hi = trace.window
    return sum(1 for e in trace.host if e.name == SPAN_PREFIX + name
               and lo <= e.end_ns <= hi)


# ---------------------------------------------------------------------- HLO
_DTYPE_BYTES = {"pred": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
                "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s4": 0.5, "u4": 0.5}
_SHAPE = re.compile(r"\b([a-z]+[0-9a-z]*)\[([0-9,]*)\]")


def shape_bytes(text: str) -> float:
    """Bytes of every array shape written in ``text`` (``f32[11,384]``)."""
    total = 0.0
    for dtype, dims in _SHAPE.findall(text):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            if not dtype.startswith("f8"):
                continue
            size = 1
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += size * n
    return total


def _balanced(text: str, start: int, open_: str, close: str) -> str:
    """The text inside the bracket that opens at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == open_:
            depth += 1
        elif text[i] == close:
            depth -= 1
            if depth == 0:
                return text[start + 1:i]
    raise ValueError(f"unbalanced {open_}{close}: {text[:200]}")


def custom_call_bytes(hlo_text: str,
                      target: str = "tpu_custom_call") -> Dict[str, float]:
    """For every ``custom-call`` to ``target`` in the HLO text, the bytes
    of its results and operands, by instruction name.  Operand shapes are
    read from ``operand_layout_constraints`` where the compiler wrote it
    (TPU), else from the operand list."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        if f'custom_call_target="{target}"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
                     r"custom-call\(", line)
        if not m:
            continue
        key = "operand_layout_constraints={"
        at = line.find(key)
        ops = _balanced(line, at + len(key) - 1, "{", "}") if at >= 0 \
            else _balanced(line, m.end() - 1, "(", ")")
        out[m.group(1)] = shape_bytes(m.group(2)) + shape_bytes(ops)
    return out


def kernel_picker(names: Sequence[str]):
    """Accepts the trace names of the given HLO instructions."""
    known = set(names)
    return lambda name: instruction(name) in known


def kernel_bytes_and_ns(trace: Trace, dev: int,
                        cc_bytes: Dict[str, float]) -> Tuple[float, float]:
    """Bytes moved and device time of every execution of a custom call
    inside the window."""
    lo, hi = trace.window
    nbytes, ns = 0.0, 0.0
    for e in trace.devices.get(dev, ()):
        name = instruction(e.name)
        if name in cc_bytes and e.start_ns >= lo and e.end_ns <= hi:
            nbytes += cc_bytes[name]
            ns += e.dur_ns
    return nbytes, ns


def dump(trace: Trace, path: str) -> None:
    with open(path, "w") as fh:
        json.dump(trace.to_json(), fh)


def load(path: str) -> Trace:
    with open(path) as fh:
        return Trace.from_json(json.load(fh))
