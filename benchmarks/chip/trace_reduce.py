"""Reduce a profiler trace to device busy time, module and kernel time,
and idle gaps named by the host span they fell in.

`capture` records JAX's profiler trace of a block of work; `load` reads
the ``.xplane.pb`` it wrote into a `TraceData` of plain intervals (all in
the trace's own nanosecond clock); the functions below reduce those. The
benchmark's host spans are `jax.profiler.TraceAnnotation` events whose
names start with ``bench.``; they sit on the same clock as the device
events, which is what lets an idle gap be named.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import pathlib
import shutil

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops that hold other ops of the same line (their bodies run inside them)
CONTAINERS = ("while", "conditional", "call")
# the host runtime's launch of one device program: a device cannot start
# a program before the host issued it
ISSUE_EVENT = "tpu::System::Execute=>IssueSequencedEvent"
MOSAIC = 'custom_call_target="tpu_custom_call"'


def short_op(text: str) -> str:
    """'%fusion.3 = bf16[2]{0} fusion(%p), kind=kLoop' -> 'fusion.3 fusion':
    the op's name and opcode from the HLO text the TPU trace names ops
    by. A Pallas (Mosaic) kernel is a custom call; its name says which
    kernel only where the program kept it, so it is marked 'mosaic'."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text
    name = name.lstrip("%")
    if rest.startswith("("):            # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.strip().partition("(")[0]
    if MOSAIC in text:
        opcode += " mosaic"
    return f"{name} {opcode}"


def opcode(short: str) -> str:
    return short.split(" ")[1] if " " in short else ""


@dataclasses.dataclass
class Interval:
    name: str
    start: float          # ns
    end: float            # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class TraceData:
    """Per device: the operations and the module (program) executions it
    ran; and the benchmark's host spans."""
    ops: dict[str, list[Interval]]
    modules: dict[str, list[Interval]]
    spans: list[Interval]


@contextlib.contextmanager
def capture(out_dir: str | pathlib.Path):
    """Profile the block into ``out_dir`` (emptied first)."""
    import jax

    out_dir = pathlib.Path(out_dir)
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    # host: the annotations and runtime events, no Python call tracing
    # (which would multiply the trace's size and the host's overhead)
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        yield out_dir
    finally:
        jax.profiler.stop_trace()


def find_xplane(out_dir: str | pathlib.Path) -> str:
    found = sorted(glob.glob(os.path.join(
        str(out_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return found[-1]


def load(xplane_path: str) -> TraceData:
    """Device ops and module runs, and the benchmark's host spans, all on
    the host's clock (see `device_offset`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    ops: dict[str, list[Interval]] = {}
    modules: dict[str, list[Interval]] = {}
    spans: list[Interval] = []
    issues: list[float] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst, short = ops.setdefault(plane.name, []), short_op
                elif line.name == MODULES_LINE:
                    dst, short = modules.setdefault(plane.name, []), str
                else:
                    continue
                dst.extend(Interval(short(e.name), e.start_ns, e.end_ns)
                           for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Interval(e.name, e.start_ns, e.end_ns))
                    elif e.name == ISSUE_EVENT:
                        issues.append(e.start_ns)
    issues.sort()
    for d in (ops, modules):
        for v in d.values():
            v.sort(key=lambda i: i.start)
    for dev, mods in modules.items():
        shift = device_offset(mods, issues)
        for iv in mods + ops.get(dev, []):
            iv.start += shift
            iv.end += shift
    spans.sort(key=lambda i: i.start)
    return TraceData(ops, modules, spans)


def device_offset(modules: list[Interval], issues: list[float]) -> float:
    """Nanoseconds to add to a device's event times to put them on the
    host's clock. The trace's device clock is off from the host's by
    about a millisecond (1.2 ms on a v5e), which matters against steps of
    ten. Each program run is paired, in order, with the host's issue of
    it; a run cannot start before its issue, and the run that waited
    least started right after it, so the offset is the largest issue time
    less start time. 0 where the counts differ."""
    if not modules or len(modules) != len(issues):
        return 0.0
    return max(i - m.start for i, m in zip(issues, modules))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) pieces of ``intervals`` clipped to [lo, hi)."""
    pieces = sorted((max(i.start, lo), min(i.end, hi)) for i in intervals
                    if i.end > lo and i.start < hi)
    merged: list[list[float]] = []
    for s, e in pieces:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def window(td: TraceData, name: str = SPAN_PREFIX + "traced"
           ) -> tuple[float, float]:
    """The [start, end) of the host span that marks the traced window."""
    for s in td.spans:
        if s.name == name:
            return s.start, s.end
    raise ValueError(f"the trace holds no {name!r} span")


def module_base(name: str) -> str:
    """'jit_step(123)' -> 'jit_step': the program's name without the id
    the runtime appends."""
    return name.split("(")[0].strip()


def module_runs(td: TraceData, device: str, prefix: str, lo: float,
                hi: float) -> list[Interval]:
    """Executions of the program named ``prefix``, inside
    the window, in order."""
    return [m for m in td.modules.get(device, ())
            if module_base(m.name) == prefix
            and m.start >= lo and m.end <= hi]


def ops_inside(td: TraceData, device: str, outer: list[Interval],
               match) -> list[list[Interval]]:
    """For each interval of ``outer``, the device operations whose name
    satisfies ``match`` and whose midpoint lies inside it."""
    ops = [o for o in td.ops.get(device, ()) if match(o.name)]
    mids = [(o.start + o.end) / 2 for o in ops]
    out = []
    for iv in outer:
        a = bisect.bisect_left(mids, iv.start)
        b = bisect.bisect_right(mids, iv.end)
        out.append(ops[a:b])
    return out


def top_ops(td: TraceData, lo: float, hi: float, n: int = 10
            ) -> list[tuple[str, float]]:
    """Device operations by total seconds inside the window, summed over
    devices and over executions of the same operation; loops and calls,
    whose bodies are listed on their own, are left out."""
    tot: dict[str, float] = {}
    for ops in td.ops.values():
        for o in ops:
            if o.end > lo and o.start < hi \
                    and opcode(o.name) not in CONTAINERS:
                tot[o.name] = tot.get(o.name, 0.0) + (
                    min(o.end, hi) - max(o.start, lo)) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(td: TraceData, device: str, lo: float, hi: float,
              n: int = 10) -> list[tuple[str, float]]:
    """The longest spans of the window in which ``device`` ran nothing,
    each named by the innermost host span that holds its midpoint
    ('host' where none does)."""
    busy = union(td.ops.get(device, ()), lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        inner = [sp for sp in td.spans if sp.start <= mid <= sp.end
                 and sp.name != SPAN_PREFIX + "traced"]
        name = min(inner, key=lambda sp: sp.dur).name if inner else "host"
        named.append((name, (e - s) * 1e-9))
    return named


def coverage(td: TraceData, device: str, lo: float, hi: float) -> float:
    """Share of the window between the first and the last device op: well
    under 1 where the profiler dropped events."""
    ops = [o for o in td.ops.get(device, ()) if o.end > lo and o.start < hi]
    if not ops:
        return 0.0
    return (max(o.end for o in ops) - min(o.start for o in ops)) / (hi - lo)
