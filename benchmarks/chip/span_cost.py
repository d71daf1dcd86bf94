"""What the program's own spans cost while the profiler records.

    python3 benchmarks/chip/span_cost.py --workload <cell> --seed <n> \
        [--sessions 8]

Builds the cell's program as a traced run of `run.py` does, warms it up
and primes its loop, then drives its traffic through ``--sessions``
profiler sessions of `harness.TRACE_S` seconds. Inside each session the
engine steps alternate between the program's spans on and off (on the
off steps `repro.serving.telemetry` sees no trace recording, as when the
profiler is off), and each step's wall time is kept. Steps that ran a
prefill chunk are set apart, since their work varies with the prompt;
the cost of the spans is the difference of the mean wall times of the
decode-only steps, on less off, with its standard error. Prints one
JSON line (ms). Needs the chip.
"""

import argparse
import json
import math
import pathlib
import statistics
import sys

HERE = pathlib.Path(__file__).resolve().parent


class _NotRecording:
    """`jax.profiler.TraceAnnotation` as the program's spans see it while
    no trace records: they open nothing."""

    @staticmethod
    def is_enabled() -> bool:
        return False


def _summary(on: list, off: list) -> dict:
    out = {"steps_on": len(on), "steps_off": len(off)}
    if len(on) < 2 or len(off) < 2:
        return out
    m_on, m_off = statistics.fmean(on), statistics.fmean(off)
    se = math.sqrt(statistics.variance(on) / len(on)
                   + statistics.variance(off) / len(off))
    out.update(mean_on_ms=m_on, mean_off_ms=m_off,
               on_less_off_ms=m_on - m_off, se_ms=se)
    return out


def measure(h, sessions: int, trace_dir) -> dict:
    """Wall times of ``h``'s engine steps in ``sessions`` traced windows,
    the program's spans on every other step; ``h`` warmed up and
    primed."""
    from repro.serving import telemetry as TM

    import harness as H

    real = TM.TraceAnnotation
    walls = {(on, decode): [] for on in (True, False)
             for decode in (True, False)}
    inner = h.step
    state = {"on": False}

    def step(record):
        state["on"] = on = not state["on"]
        TM.TraceAnnotation = real if on else _NotRecording
        try:
            t = H.CLOCK()
            finished = inner(record=True)
            wall = H.CLOCK() - t
        finally:
            TM.TraceAnnotation = real
        walls[on, not h.steps[-1].prefills].append(wall * 1e3)
        return finished

    h.step = step
    try:
        for _ in range(sessions):
            h.window(H.TRACE_S, trace_dir)
    finally:
        h.step = inner
    return {"decode_steps": _summary(walls[True, True], walls[False, True]),
            "prefill_steps": _summary(walls[True, False],
                                      walls[False, False])}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sessions", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness as H

    H.strip_program_env()
    sys.path.insert(0, str(H.REPO / "src"))
    import jax

    H.enable_compile_cache()
    if jax.devices()[0].platform == "cpu":
        print("[span_cost] JAX finds no accelerator", file=sys.stderr)
        return 1
    h = H.Harness(H.load_cell(args.workload), args.seed, tracing=True)
    h.warm_up()
    h.prepare(args.sessions * H.TRACE_S + 10.0)
    h.prime()
    got = measure(h, args.sessions, H.REPO / ".bench_trace" / "span_cost")
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "sessions": args.sessions, **got}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
