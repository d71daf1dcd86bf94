"""Find an open-loop cell's knee: the highest arrival rate it sustains.

    python3 benchmarks/chip/sweep.py --workload <open-loop cell> \
        --seed 5 --seconds 15 --rates 4,8,12,16

In one process (set up once), the cell's traffic is offered at each rate
in turn for a window, and drained in between. Per rate, one JSON line:
offered and completed output tokens/s, the p90 time to first token, and
the requests still waiting or running when the window closed. Past the
knee the completed rate stops following the offered one and the backlog
at the close grows with the window. The benchmark's runs never sweep:
a cell's rate is fixed in its traffic file.
"""

import dataclasses
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness as H

    H.strip_program_env()
    sys.path.insert(0, str(H.REPO / "src"))
    import jax

    H.enable_compile_cache()
    if jax.devices()[0].platform == "cpu":
        print("[sweep] no accelerator", file=sys.stderr)
        return 1
    cell = H.load_cell(args.workload)
    if cell.traffic.loop != "open":
        print("[sweep] not an open-loop cell", file=sys.stderr)
        return 1
    h = H.Harness(cell, args.seed)
    h.warm_up()
    out_reader = H.load_reader("output_tok_s")
    ttft_reader = H.load_reader("ttft_p90_ms")
    for rate in (float(r) for r in args.rates.split(",")):
        h.cell = dataclasses.replace(cell, traffic=dataclasses.replace(
            cell.traffic, rate_per_s=rate))
        h.steps = []
        h.prepare(args.seconds)
        t0, t1 = h.window(args.seconds)
        backlog = h.engine.scheduler.pending + h.engine.pool.active_slots
        h.drain()
        run = H.Run(cell=h.cell, peaks={}, chips=1,
                    seconds=args.seconds, t0=t0, t1=t1,
                    setup_s=0.0, records=list(h.records.values()),
                    steps=h.steps, hot_window=h.backend.hot_window)
        due = run.due_in_window()
        mean_out = sum(s.out_len for s in cell.traffic.shapes) \
            / len(cell.traffic.shapes)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "offered_tok_s": rate * mean_out,
            "output_tok_s": out_reader.read(run),
            "ttft_p90_ms": ttft_reader.read(run),
            "backlog_at_close": backlog}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
