"""The profiler trace of a window, and its reduction to device busy time
and to the breakdown of where the time went.

The run opens a ``bench.window`` annotation around its measured window;
the reduction reads device operations from the ``XLA Ops`` line and
programs from the ``XLA Modules`` line of each ``/device:<kind>:<n>``
plane, and host spans from every host line, all on the profiler's one
clock.  On a TPU v5e with JAX 0.9 an operation's event is named by its
HLO instruction and carries no name stack, so an operation is known by
its program and instruction, not by the kernel that emitted it.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import gzip
import heapq
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class Op:
    device: int
    name: str             # the HLO instruction, as the event names it
    module: str           # the program it ran in
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class HostSpan:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    ops: list[Op]
    host: list[HostSpan]
    window: tuple[float, float]
    devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the block into ``log_dir``, with the Python tracer off (it
    would record every Python call of the host)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _device_index(plane_name: str) -> int | None:
    m = re.fullmatch(r"/device:([A-Za-z_]+):(\d+)", plane_name)
    if not m or m.group(1).upper() == "CPU":
        return None
    return int(m.group(2))


def _device_ops(plane, dev: int) -> list[Op]:
    """The plane's operations, each with the program whose run covers its
    start."""
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return []
    modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                     for ev in lines[MODULES_LINE].events) \
        if MODULES_LINE in lines else []
    ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                 for ev in lines[OPS_LINE].events)
    out, m = [], 0
    for start, dur, name in ops:
        while m < len(modules) and modules[m][1] < start:
            m += 1
        module = modules[m][2] if m < len(modules) \
            and modules[m][0] <= start else ""
        out.append(Op(dev, name, module, start, start + dur))
    return out


def load(path: str, devices: int = 1) -> Trace:
    """Read an ``.xplane.pb`` file, gzipped or not, or the newest one under
    a directory: the ops of devices ``0..devices-1`` and every host span,
    clipped to the window."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops: list[Op] = []
    host: list[HostSpan] = []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            if dev < devices:
                ops.extend(_device_ops(plane, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append(HostSpan(ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
    spans = [h for h in host if h.name == WINDOW_SPAN]
    if spans:
        window = (spans[0].start_ns, spans[0].end_ns)
    elif ops:
        window = (min(o.start_ns for o in ops), max(o.end_ns for o in ops))
    else:
        window = (0.0, 0.0)
    lo, hi = window
    clipped = [dataclasses.replace(o, start_ns=max(o.start_ns, lo),
                                   end_ns=min(o.end_ns, hi))
               for o in ops if o.end_ns > lo and o.start_ns < hi]
    return Trace(clipped, host, window, devices)


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    total = 0.0
    for d in range(trace.devices):
        total += sum(e - s for s, e in _union(
            (o.start_ns, o.end_ns) for o in trace.ops if o.device == d))
    return total / 1e9 / trace.devices


def short_name(hlo: str) -> str:
    """``%while.138 = (s32[]{...}, ...) while(...)`` -> ``while.138 while
    (s32[], ...)``: the instruction, its opcode and its result shape
    without layouts."""
    m = re.match(r"%?(\S+) = ", hlo)
    if not m:
        return hlo[:120]
    rest = hlo[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    shape = re.sub(r"\{[^{}]*\}", "", shape).replace("/*index=5*/", "")
    return f"{m.group(1)} {opcode} {shape[:80]}"


def _label(op: Op) -> str:
    return f"{op.module}: {short_name(op.name)}" if op.module \
        else short_name(op.name)


def breakdown(trace: Trace) -> dict:
    """The device operations that took most time, summed by program and
    instruction, and the idle time of device 0, summed by the innermost host
    span that covered each gap's middle."""
    by_op: dict[str, float] = collections.defaultdict(float)
    for o in trace.ops:
        by_op[_label(o)] += (o.end_ns - o.start_ns) / 1e9 / trace.devices
    busy = _union((o.start_ns, o.end_ns) for o in trace.ops
                  if o.device == 0)
    lo, hi = trace.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted((h for h in trace.host if h.name != WINDOW_SPAN),
                  key=lambda h: h.start_ns)
    by_host: dict[str, float] = collections.defaultdict(float)
    # sweep the gaps' middles in order; the heap holds the host spans
    # begun so far, shortest first, and drops those that ended before
    active: list[tuple[float, int, HostSpan]] = []
    nxt = 0
    for s, e in gaps:
        mid = (s + e) / 2
        while nxt < len(host) and host[nxt].start_ns <= mid:
            h = host[nxt]
            heapq.heappush(active, (h.end_ns - h.start_ns, nxt, h))
            nxt += 1
        while active and active[0][2].end_ns < mid:
            heapq.heappop(active)
        name = active[0][2].name if active else "no host span"
        by_host[name] += (e - s) / 1e9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:TOP]]

    return {"device_ops": top(by_op), "idle_gaps": top(by_host)}
