"""Readings that set a cell's limit: the program's, the control's, and
those of faults planted in the served path.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --seconds 10 [--control 2] \
        [--faults state_unchanged,cold_zero --fault-seeds 14,15]

For each seed, in one process: serve the cell's traffic through the
program for a short window at the cell's own load and sizes, drain, and
hold the same seeded sample as a run does to the reference. The first
``--control`` seeds also score the same prompts and served tokens with
the control, the reference computed in int8 in the program's place: at
each position the token the control puts first is scored against the
float32 reference. Then each fault is planted in the decode step and
read on each of ``--fault-seeds``. One JSON line per reading. The
benchmark's own runs never run the control or a fault.
"""

import contextlib
import dataclasses
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
FAULTS = ("state_unchanged", "cold_zero")


@contextlib.contextmanager
def planted(fault: str | None):
    """The program's decode step, broken underneath: ``state_unchanged``
    hands back the KV state it was given (the new token's keys and values
    never stored); ``cold_zero`` zeroes every int8 cold-tier scale before
    the step reads the pool (the cold tier reads as nothing)."""
    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    import jax
    import jax.numpy as jnp
    from repro.serving import backend as B

    real = B._JittedBackend.decode_step

    @jax.jit
    def zero_cold(cache):
        return jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros_like(x)
            if "cold_scale" in jax.tree_util.keystr(p) else x, cache)

    def broken(self, toks, state, pos, active):
        if fault == "cold_zero":
            state = dataclasses.replace(state, cache=zero_cold(state.cache))
            return real(self, toks, state, pos, active)
        ntoks, _ = real(self, toks, state, pos, active)
        return ntoks, state

    B._JittedBackend.decode_step = broken
    try:
        yield
    finally:
        B._JittedBackend.decode_step = real


def readings(cell, seed: int, seconds: float, control: bool,
             fault: str | None = None) -> dict:
    """Serve ``cell`` for a window, drain, and score the run's seeded
    sample with the program's tokens (and the control's, with
    ``control``), with ``fault`` planted if given."""
    import harness as H

    t = time.perf_counter()
    with planted(fault):
        h = H.Harness(cell, seed)
        h.warm_up()
        h.prepare(seconds)
        h.prime()
        t0, t1 = h.window(seconds)
        h.drain()
    due = [r for r in h.records.values() if t0 <= r.due < t1]
    params = h.params
    h.free_program()
    row = {"workload": cell.name, "seed": seed, "fault": fault,
           "requests": len(due),
           "program": H.check(params, cell, due, seed)}
    if control:
        row["control"] = H.check(params, cell, due, seed, control=True)
    row["wall_s"] = time.perf_counter() - t
    return row


def _ints(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=0,
                    help="score the control on the first N seeds")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness as H

    H.strip_program_env()
    sys.path.insert(0, str(H.REPO / "src"))
    import jax

    H.enable_compile_cache()
    if jax.devices()[0].platform == "cpu":
        print("[calibrate] no accelerator", file=sys.stderr)
        return 1
    cell = H.load_cell(args.workload)
    for i, seed in enumerate(_ints(args.seeds)):
        row = readings(cell, seed, args.seconds, i < args.control)
        print(json.dumps(row), flush=True)
    for fault in (f for f in args.faults.split(",") if f):
        for seed in _ints(args.fault_seeds):
            row = readings(cell, seed, args.seconds, False, fault)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
