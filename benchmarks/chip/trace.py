"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

    tr = load(path)                 # device planes /device:TPU:N
    tr.busy_s(), tr.window_s()      # union of device operations, window
    tr.op_s(r"^paged_attention")    # device time of operations by name
    tr.module_s(r"serve_step")      # device time of one compiled program
    tr.idle_gaps()                  # gaps, each with the host span open
    breakdown(tr)                   # top operations and longest gaps
    attach_hlo(tr, texts)           # each op's scope and class from the HLO
    tr.scope_s(r"/kv_cache/"), tr.cls_s("movement", r"serve_step")

Device operations are the events of a device plane's "XLA Ops" line (not
"Async XLA Ops", whose copies and collectives are in flight while other
operations run), or, on a plane without one (the CPU backend), the events
that carry an `hlo_op` stat. An operation is named by its HLO instruction
(`paged_attention.5`). Each operation's program comes from its `hlo_module` stat,
or from the "XLA Modules" event it lies in. Host spans are the events on
host planes whose names the caller lists (the benchmark's own
`TraceAnnotation`s). The window is the first host span named "window", or
the extent of the device operations where there is none. Times are in
nanoseconds on the profiler's clock, which device and host planes share.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

TPU_PLANE = r"^/device:TPU:(\d+)$"


@dataclass
class Op:
    device: int
    name: str
    module: str
    start: float
    end: float
    self_ns: float = 0.0
    scope: str = ""     # the instruction's op_name metadata (`attach_hlo`)
    cls: str = ""       # its class (`attach_hlo`); "" where not joined


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    window: Tuple[float, float]
    devices: List[int] = field(default_factory=list)

    # ------------------------------------------------------------- queries
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, ops: Iterable[Op]) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return [(max(o.start, lo), min(o.end, hi)) for o in ops
                if o.end > lo and o.start < hi]

    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        return union(self._clip(o for o in self.ops if o.device == device))

    def busy_s_per_device(self) -> Dict[int, float]:
        return {d: sum(e - s for s, e in self.busy_intervals(d)) * 1e-9
                for d in self.devices}

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        per = self.busy_s_per_device()
        return sum(per.values()) / max(len(per), 1)

    def select(self, name: Optional[str] = None,
               module: Optional[str] = None) -> List[Op]:
        lo, hi = self.window
        return [o for o in self.ops if o.start >= lo and o.end <= hi
                and (name is None or re.search(name, o.name))
                and (module is None or re.search(module, o.module))]

    def op_s(self, name: str, module: Optional[str] = None) -> float:
        """Summed self time of the matching operations, over all devices."""
        return sum(o.self_ns for o in self.select(name, module)) * 1e-9

    def scope_s(self, pattern: str, module: Optional[str] = None) -> float:
        """Summed self time of the operations whose scope matches."""
        return sum(o.self_ns for o in self.select(module=module)
                   if re.search(pattern, o.scope)) * 1e-9

    def cls_s(self, cls: str, module: Optional[str] = None) -> float:
        """Summed self time of the operations of one class."""
        return sum(o.self_ns for o in self.select(module=module)
                   if o.cls == cls) * 1e-9

    def op_count(self, name: str, module: Optional[str] = None) -> int:
        return len(self.select(name, module))

    def module_s(self, module: str) -> float:
        """Device time of a program: the union of its operations' intervals
        on each device, summed over the devices."""
        ops = self.select(module=module)
        return sum(sum(e - s for s, e in union(
            (o.start, o.end) for o in ops if o.device == d))
            for d in self.devices) * 1e-9

    def span_at(self, t: float) -> str:
        """Innermost host span open at time t, or "none": the latest to
        start of the few spans that began before t and have not ended."""
        if not hasattr(self, "_inner"):
            self._inner = sorted((s for s in self.spans
                                  if s.name != "window"),
                                 key=lambda s: s.start)
            self._starts = [s.start for s in self._inner]
        i = bisect.bisect_right(self._starts, t) - 1
        for s in self._inner[max(i - 7, 0):i + 1][::-1]:
            if t < s.end:
                return s.name
        return "none"

    def idle_gaps(self, device: Optional[int] = None
                  ) -> List[Tuple[str, float]]:
        """(host span, seconds) of each gap in the window in which no
        operation ran, on `device` or on each device."""
        out = []
        for d in ([device] if device is not None else self.devices):
            t = self.window[0]
            for s, e in self.busy_intervals(d) + [(self.window[1],) * 2]:
                if s > t:
                    out.append((self.span_at((s + t) / 2), (s - t) * 1e-9))
                t = max(t, e)
        return out


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(ops: List[Op]) -> None:
    """Self time of operations on one line, where a parent (a loop, say)
    holds its children: its duration less that of its direct children."""
    stack: List[Op] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


def op_name(text: str) -> str:
    """`%paged_attention.5 = bf16[...] custom-call(...)` (a TPU trace
    names an operation by its HLO text) -> `paged_attention.5`."""
    return text.split(" = ", 1)[0].lstrip("%")


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def load(path: str, span_names: Sequence[str] = (),
         device_plane: str = TPU_PLANE) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    wanted = set(span_names) | {"window"}
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
        m = re.match(device_plane, plane.name)
        if m is None:
            continue
        dev = int(m.group(1)) if m.groups() and m.group(1) else 0
        devices.append(dev)
        lines = {line.name: line for line in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in (lines["XLA Modules"].events
                                 if "XLA Modules" in lines else ()))
        starts = [m_[0] for m_ in mods]

        def module_of(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else ""

        op_lines = ([lines["XLA Ops"]] if "XLA Ops" in lines
                    else [ln for ln in plane.lines
                          if ln.name != "XLA Modules"])
        for line in op_lines:
            line_ops = []
            for ev in line.events:
                st = None
                if line.name != "XLA Ops":
                    st = _stats(ev)
                    if "hlo_op" not in st:
                        continue
                start = ev.start_ns
                module = (st or {}).get("hlo_module") or module_of(start)
                line_ops.append(Op(dev, op_name(ev.name), str(module), start,
                                   start + ev.duration_ns))
            _self_times(line_ops)
            ops.extend(line_ops)
    spans.sort(key=lambda s: s.start)
    win = next((s for s in spans if s.name == "window"), None)
    if win is not None:
        window = (win.start, win.end)
    elif ops:
        window = (min(o.start for o in ops), max(o.end for o in ops))
    else:
        window = (0.0, 0.0)
    return Trace(ops, spans, window, sorted(set(devices)))


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time (self time, averaged over
    the devices) and the longest idle gaps by the host span open in them."""
    per: Dict[str, float] = {}
    for o in tr.select():
        key = f"{_short(o.module)}/{o.name}" if o.module else o.name
        per[key] = per.get(key, 0.0) + o.self_ns * 1e-9
    n = max(len(tr.devices), 1)
    device_ops = sorted(([k, v / n] for k, v in per.items()),
                        key=lambda kv: -kv[1])[:top]
    gaps = sorted(([s, g] for s, g in tr.idle_gaps()),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": device_ops, "idle_gaps": gaps}


def _short(module: str) -> str:
    """`jit_serve_step(123)` -> `serve_step`."""
    name = re.sub(r"\(.*$", "", module)
    return name[4:] if name.startswith("jit_") else name


# ----------------------------------------------------------- HLO join
MATMUL = {"dot", "convolution"}
KERNEL = {"custom-call"}
MOVEMENT = {"copy", "bitcast", "reshape", "transpose", "slice",
            "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
            "broadcast", "convert"}
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element"}
CONTROL = {"while", "call", "conditional"}
CLASSES = ("kernel", "matmul", "movement", "control", "other")

_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')


def _opcode(rest: str) -> str:
    """The opcode of `<shape> <opcode>(...)`; a tuple shape is in
    parentheses and may hold spaces."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    m = re.match(r"\S*\s+([\w\-]+)\(", rest[i:])
    return m.group(1) if m else ""


def classify(opcodes: Iterable[str]) -> str:
    """The class of an instruction from its opcodes (see `attach_hlo`)."""
    ops = set(opcodes)
    if ops & MATMUL:
        return "matmul"
    if ops & KERNEL:
        return "kernel"
    if ops and ops <= MOVEMENT | PLUMBING:
        return "movement"
    if ops & CONTROL:
        return "control"
    return "other"


def parse_hlo(text: str) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """HLO text (`Compiled.as_text()`, one or more modules) -> for each
    module's short name, {instruction name: (op_name, class)}."""
    parsed = []   # per module: {name: (opcode, op_name, calls)}, computations
    comp: Optional[List[str]] = None
    for line in text.splitlines():
        m = _MODULE.match(line)
        if m:
            parsed.append((_short(m.group(1)), {}, {}))
            comp = None
        elif not parsed:
            continue
        elif comp is None:
            m = _COMPUTATION.match(line)
            if m:
                comp = parsed[-1][2].setdefault(m.group(1), [])
        elif line.strip() == "}":
            comp = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                rest = m.group(2)
                op_name = _OP_NAME.search(rest)
                parsed[-1][1][m.group(1)] = (
                    _opcode(rest), op_name.group(1) if op_name else "",
                    _CALLS.findall(rest))
                comp.append(m.group(1))

    out = {}
    for module, insts, comps in parsed:
        def opcodes(name: str) -> set:
            opcode, _, calls = insts[name]
            if opcode != "fusion":
                return {opcode}
            return set().union(*(opcodes(i) for c in calls
                                 for i in comps.get(c, ())))
        out[module] = {name: (op_name, classify(opcodes(name)))
                       for name, (_, op_name, _) in insts.items()}
    return out


def attach_hlo(tr: Trace, texts: Iterable[str]) -> List[str]:
    """Joins each device operation to its instruction in the compiled
    programs' HLO text, by (program, instruction name), and sets its
    `scope` (the instruction's `op_name` metadata, or "") and `cls`.
    Returns the short names of the programs found in the texts.

    The class of a fusion is that of the opcodes of its fused computation,
    walked through the fusions nested in it; of any other instruction,
    that of its own opcode. In this order:

    - `matmul`: a `dot` or `convolution` among them;
    - `kernel`: a `custom-call` (a Pallas kernel, or a helper of XLA's);
    - `movement`: nothing but `copy`, `bitcast`, `reshape`, `transpose`,
      `slice`, `dynamic-slice`, `dynamic-update-slice`, `concatenate`,
      `pad`, `broadcast` and `convert`, with the plumbing opcodes
      `parameter`, `constant`, `tuple` and `get-tuple-element`;
    - `control`: a `while`, `call` or `conditional`;
    - `other`: everything else.

    An operation whose instruction is not found keeps `cls` "". Nothing
    else of the trace changes."""
    modules: Dict[str, Dict[str, Tuple[str, str]]] = {}
    for text in texts:
        modules.update(parse_hlo(text))
    for o in tr.ops:
        hit = modules.get(_short(o.module), {}).get(o.name)
        if hit is not None:
            o.scope, o.cls = hit
    return list(modules)


def join_shares(tr: Trace, module: str) -> Tuple[float, float]:
    """Shares of a program's self time in the window whose operation found
    its instruction, and whose instruction has no `op_name`."""
    ops = tr.select(module=module)
    total = sum(o.self_ns for o in ops)
    if total <= 0:
        return 0.0, 0.0
    return (sum(o.self_ns for o in ops if o.cls) / total,
            sum(o.self_ns for o in ops if o.cls and not o.scope) / total)
