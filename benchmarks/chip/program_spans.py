"""The program's own spans in a traced run, and the per-step split of
device-idle time they give.

The serving program marks its work with profiler annotations named
``repro.<layer>.<what>`` (`repro.serving.telemetry.span`): each
``repro.engine.step``, the engine's phases inside it, and the backend's
``repro.backend.<program>.put`` (host-to-device copies of a jitted
program's arguments) and ``.launch`` (the call) around every program it
runs, and ``repro.engine.fetch`` around the host's reads of a
program's result, which wait for the device. The decode launch carries the KV pool's counters as arguments
(``kv_pool_bytes``, ``kv_live_bytes``).

`trace_reduce.load` keeps the benchmark's ``bench.`` spans only, so the
program's are read here from the same ``.xplane.pb``, which a run of
``run.py`` writes under ``.bench_trace/<cell>`` of the checkout. The
file is taken as the run's own only where its ``bench.traced`` span is
the run's traced window; a program without these spans gives no spans,
and its readers no value.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import trace_reduce as TR
from harness import REPO

PREFIX = "repro."
STEP = "repro.engine.step"
BACKEND = "repro.backend."
# the spans of an engine step in which the host works for the backend's
# programs: copying their arguments, launching them, and reading their
# results back (a wait for the device and the copy to the host)
BACKEND_SIDE = (BACKEND, "repro.engine.fetch")
DECODE_LAUNCH = "repro.backend.step.launch"


@dataclasses.dataclass
class Span:
    name: str
    start: float          # ns, the host's clock
    end: float
    args: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


def load(xplane: str) -> tuple[list[Span], tuple[float, float] | None]:
    """The program's spans in start order, and the [start, end) of the
    benchmark's ``bench.traced`` span (None where the file has none)."""
    from jax.profiler import ProfileData

    spans: list[Span] = []
    window = None
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name, e.start_ns, e.end_ns,
                                      dict(e.stats)))
                elif e.name == TR.SPAN_PREFIX + "traced":
                    window = (e.start_ns, e.end_ns)
    spans.sort(key=lambda s: s.start)
    return spans, window


@functools.lru_cache(maxsize=1)
def _load_once(xplane: str, mtime_ns: int, size: int):
    return load(xplane)


def trace_dir(run):
    """Where `harness.main` has the profiler write ``run``'s trace."""
    return REPO / ".bench_trace" / run.cell.name


def of_run(run) -> list[Span] | None:
    """The program's spans of a traced run (read once for all readers),
    or None without a trace of this run or without such spans."""
    if run.trace is None:
        return None
    try:
        path = TR.find_xplane(trace_dir(run))
    except FileNotFoundError:
        return None
    st = os.stat(path)
    spans, window = _load_once(path, st.st_mtime_ns, st.st_size)
    if window != run.trace_window():
        return None
    return spans or None


def inside(spans: list[Span], name_prefix: str | tuple[str, ...],
           lo: float, hi: float) -> list[Span]:
    return [s for s in spans if s.name.startswith(name_prefix)
            and lo <= s.start and s.end <= hi]


def idle_split(td: TR.TraceData, device: str, spans: list[Span],
               lo: float, hi: float) -> list[tuple[float, float]]:
    """For each ``repro.engine.step`` inside [lo, hi): the nanoseconds
    in which ``device`` ran nothing while a span of `BACKEND_SIDE` was
    open (copying arguments, launching, reading results back), and those
    of the rest of the step (the engine's own work)."""
    ops = td.ops.get(device, ())
    out = []
    for step in inside(spans, STEP, lo, hi):
        near = [o for o in ops if o.end > step.start and o.start < step.end]
        idle = step.dur - TR.busy_ns(near, step.start, step.end)
        pieces = TR.union(inside(spans, BACKEND_SIDE, step.start,
                                 step.end),
                          step.start, step.end)
        launch = sum((e - s) - TR.busy_ns(near, s, e) for s, e in pieces)
        out.append((launch, idle - launch))
    return out


def kv_live_share(spans: list[Span], lo: float, hi: float
                  ) -> float | None:
    """Sum of ``kv_live_bytes`` over sum of ``kv_pool_bytes`` of the
    decode launches inside [lo, hi), in %."""
    live = pool = 0
    for s in inside(spans, DECODE_LAUNCH, lo, hi):
        if "kv_pool_bytes" in s.args:
            live += s.args["kv_live_bytes"]
            pool += s.args["kv_pool_bytes"]
    return 100.0 * live / pool if pool else None
