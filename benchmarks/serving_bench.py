"""Serving throughput vs. concurrency: does continuous batching over the
shared tiered KV pool actually buy aggregate tok/s?

    PYTHONPATH=src python benchmarks/serving_bench.py --concurrency 8
    PYTHONPATH=src python benchmarks/serving_bench.py --backend sharded
    # mixed long-VQA stream, chunked prefill (Sarathi-style):
    PYTHONPATH=src python benchmarks/serving_bench.py --arch mobilevlm-1.7b \
        --image-every 2 --prompt-len 48 --gen 16 --chunk-tokens 8
    # oversubscription: clamp the DRAM budget to concurrency/F residents
    # and compare admission-blocked vs spill-backed oversubscribed:
    PYTHONPATH=src python benchmarks/serving_bench.py --arch mobilevlm-1.7b \
        --image-every 2 --prompt-len 48 --gen 16 --chunk-tokens 8 \
        --oversubscribe 2
    # compressed-spill capacity comparison: SAME DRAM + RRAM spill
    # budgets, full-precision vs int8 lanes (lane count = budget//bytes):
    PYTHONPATH=src python benchmarks/serving_bench.py --arch mobilevlm-1.7b \
        --image-every 2 --prompt-len 48 --gen 16 --chunk-tokens 8 \
        --oversubscribe 2 --spill-compress
    # prefix sharing: every request opens with the same 28-token system
    # prompt; compare slot-charged vs block-charged admission at a DRAM
    # budget of 3 worst-case slots, plus queue-free hit vs cold TTFT:
    PYTHONPATH=src python benchmarks/serving_bench.py --arch granite-3-2b \
        --prompt-len 32 --gen 16 --hot-window 48 --prefix-share 28 \
        --block-tokens 4 --dram-budget-slots 3 --requests 12

For each slot count in {1, --concurrency} the bench drains the SAME
request stream (2x the slot count, so slots recycle) through a fresh
engine twice — the first pass pays jit compilation, the second is timed
step-by-step — and reports aggregate decode throughput, per-request and
per-step (p50/p95) latency, TTFT/TBT percentiles, the simulated CHIME
tokens/J for the served trace, and the endurance audit (write-once
discipline must survive slot recycling). Steps that decode are timed
separately (decode-BEARING: some request waited on the step for its
next token, whether or not a prefill chunk co-ran): with --chunk-tokens
their p95 is bounded by one small chunk, while whole-prompt admission
(chunk 0) drags every co-resident request's next token behind a full
prompt. A third (telemetry-enabled) pass per configuration records the
simulated tier-traffic ledger — per-tier bytes, the DRAM/RRAM/compute
energy split, the engine phase breakdown and scheduler decision counts —
and asserts it reconciles bit-for-bit with ``simulated_efficiency``.
Results append to the BENCH json trajectory at
``experiments/bench/serving.json`` so successive PRs can be compared.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving import (CapacityBudget, Engine, FCFSScheduler,
                           Telemetry, aggregate_metrics, make_backend,
                           make_synthetic_requests, simulated_efficiency)
from repro.simulator.hardware import CHIME

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "experiments" / "bench" / "serving.json"


def bench_one(model, params, cfg, backend_kind: str, concurrency: int,
              n_requests: int, prompt_len: int, gen: int, max_len: int,
              mesh=None, chunk_tokens: int | None = None,
              token_budget: int | None = None,
              image_every: int = 0, priority_every: int = 0,
              dram_budget_slots: int | None = None,
              oversubscribe: float | None = None,
              n_spill: int | None = None,
              spill_compress: bool | None = None,
              idle_offload_steps: int | None = None,
              rram_spill_bytes: float | None = None,
              fused_decode: bool | None = None,
              sparse_read: float | None = None,
              weight_stream: int | None = None) -> dict:
    backend = make_backend(backend_kind, model, params,
                           num_slots=concurrency, max_len=max_len,
                           mesh=mesh, n_spill=n_spill,
                           spill_compress=spill_compress,
                           fused_decode=fused_decode,
                           sparse_read=sparse_read,
                           weight_stream=weight_stream)
    # price with the backend's RESOLVED cfg: the per-layer "streamed"
    # flags the weight-stream pricing keys off live in cost_layers(cfg)
    sim_cfg = backend.sim_context()[0]

    def fresh_engine(telemetry=None):
        # verbatim: None consults the env knobs, explicit 0 disables.
        # With a --oversubscribe comparison, the DRAM byte budget is
        # clamped to dram_budget_slots residents: the blocked baseline
        # runs at that concurrency, the oversubscribed run reclaims the
        # full slot count with spill-lane-backed admission. With
        # rram_spill_bytes, the RRAM budget for parked spill images is
        # capped too, so the lane COUNT the budget can back is
        # n_spill = rram_spill_bytes // backend.spill_lane_bytes() —
        # the capacity lever int8-compressed lanes pull.
        sched = None
        if dram_budget_slots:
            hot_b, cold_b = backend.slot_kv_bytes()
            rram = CapacityBudget.from_platform(CHIME).rram_bytes
            if rram_spill_bytes is not None:
                rram = concurrency * cold_b + rram_spill_bytes
            sched = FCFSScheduler(
                CapacityBudget(dram_budget_slots * hot_b, rram),
                hot_b, cold_b, oversubscribe=oversubscribe or 1.0,
                spill_lanes=backend.n_spill,
                lane_bytes=backend.spill_lane_bytes(),
                idle_offload_steps=idle_offload_steps)
        return Engine(backend, scheduler=sched,
                      chunk_tokens=chunk_tokens,
                      token_budget=token_budget,
                      oversubscribe=None if sched else oversubscribe,
                      idle_offload_steps=None if sched
                      else idle_offload_steps,
                      telemetry=telemetry)

    def stream(seed):
        return make_synthetic_requests(cfg, n_requests, prompt_len, gen,
                                       seed=seed, image_every=image_every,
                                       priority_every=priority_every)

    fresh_engine().run(stream(0))              # warm-up: pays compilation
    engine = fresh_engine()                    # timed pass: clean stats
    for r in stream(1):
        engine.submit(r)
    step_s, decode_step_s = [], []
    t0 = time.perf_counter()
    start = len(engine.finished)
    while not engine.idle:
        decodes_before = engine.stats["decode_steps"]
        ts = time.perf_counter()
        engine.step()
        dt = time.perf_counter() - ts
        step_s.append(dt)
        if engine.stats["decode_steps"] > decodes_before:
            # decode-heavy step: some request waited on it for its next
            # token — the TBT tail chunked prefill exists to bound
            decode_step_s.append(dt)
    wall = time.perf_counter() - t0
    done = engine.finished[start:]
    m = aggregate_metrics(done, wall)
    m["backend"] = backend_kind
    m["concurrency"] = concurrency
    # record what the engine RESOLVED (CLI flag or REPRO_SERVE_* env), so
    # env-forced chunked runs are distinguishable in the trajectory
    m["chunk_tokens"] = engine.scheduler.chunk_tokens or 0
    m["token_budget"] = engine.scheduler.token_budget or 0
    m["image_every"] = image_every
    m["oversubscribe"] = getattr(engine.scheduler, "oversubscribe",
                                 None) or 0
    m["dram_budget_slots"] = dram_budget_slots or 0
    m["spill_lanes"] = backend.n_spill
    m["spill_compress"] = bool(backend.spill_compress)
    m["spill_lane_bytes"] = backend.spill_lane_bytes()
    m["fused_decode"] = bool(backend.fused_decode)
    m["sparse_read_tau"] = float(backend.sparse_read_tau)
    m["weight_stream"] = int(backend.weight_stream)
    wb_dram, wb_rram = backend.weight_bytes()
    m["weight_bytes_dram"] = int(wb_dram)
    m["weight_bytes_rram"] = int(wb_rram)
    m["idle_offload_steps"] = getattr(engine.scheduler,
                                      "idle_offload_steps", None) or 0
    m["idle_offloads"] = engine.stats["idle_offloads"]
    m["evictions"] = engine.stats["evictions"]
    m["steps"] = len(step_s)
    m["p50_step_s"] = float(np.percentile(step_s, 50))
    m["p95_step_s"] = float(np.percentile(step_s, 95))
    if decode_step_s:
        m["decode_steps_timed"] = len(decode_step_s)
        m["p95_decode_step_s"] = float(np.percentile(decode_step_s, 95))
    m["engine_stats"] = dict(engine.stats)
    m["endurance"] = engine.endurance_report()
    m["sim"] = simulated_efficiency(
        sim_cfg, done, spill_compressed=backend.spill_compress,
        fused_decode=backend.fused_decode,
        sparse_read_tau=backend.sparse_read_tau,
        weight_stream=bool(backend.weight_stream))
    # third pass: telemetry ON over the same stream — records the
    # per-tier traffic/energy ledger + phase breakdown into the BENCH
    # trajectory, checks the ledger reconciles bit-for-bit against
    # simulated_efficiency, and measures the enabled-vs-disabled
    # wall-clock overhead (the <2% contract is on DISABLED telemetry;
    # the enabled cost recorded here is informational)
    tel = Telemetry()
    tel_engine = fresh_engine(telemetry=tel)
    for r in stream(1):
        tel_engine.submit(r)
    t0 = time.perf_counter()
    while not tel_engine.idle:
        tel_engine.step()
    tel_wall = time.perf_counter() - t0
    tel_sim = simulated_efficiency(sim_cfg, tel_engine.finished,
                                   spill_compressed=backend.spill_compress,
                                   fused_decode=backend.fused_decode,
                                   sparse_read_tau=backend.sparse_read_tau,
                                   weight_stream=bool(
                                       backend.weight_stream))
    led = tel.ledger.totals()
    summary = tel.summary()
    m["telemetry"] = {
        "tier_bytes": {k: led[k] for k in
                       ("dram_hot_ring_bytes", "rram_cold_read_bytes",
                        "rram_spill_bytes", "dram_stream_bytes",
                        "rram_stream_bytes", "sparse_skipped_bytes",
                        "weight_stream_bytes", "kv_append_bytes",
                        "ucie_bytes")},
        "energy_split_j": led["sim_energy_split_j"],
        "phase_s": summary["phase_s"],
        "decisions": summary["decisions"],
        "ledger_reconciles": (
            led["sim_energy_j"] == tel_sim["sim_energy_j"]
            and led["sim_total_s"] == tel_sim["sim_total_s"]
            and led["sim_energy_split_j"]
            == tel_sim["sim_energy_split_j"]),
        "enabled_overhead_pct": (tel_wall / max(wall, 1e-9) - 1.0) * 100,
    }
    return m


def bench_prefix_share(model, params, cfg, backend_kind: str,
                       concurrency: int, n_requests: int, prompt_len: int,
                       gen: int, max_len: int, shared: int,
                       dram_budget_slots: int, mesh=None,
                       chunk_tokens: int | None = None,
                       token_budget: int | None = None,
                       image_every: int = 0,
                       block_tokens: int | None = None) -> dict:
    """Prefix-sharing capacity + TTFT comparison at a FIXED DRAM budget.

    Every request in the stream opens with the same ``shared``-token
    system prompt (and, for VQA requests, the same image), the admission
    gate's DRAM budget is clamped to ``dram_budget_slots`` worst-case
    residents, and the SAME stream drains twice:

    - slot mode (``paged=False``): every resident is charged the
      worst-case ``max_len`` slot image, so peak concurrency is pinned
      at the budgeted slot count no matter how much of each prompt is
      duplicated work;
    - paged (``paged=True``): residents are charged their live block
      count and a prefix hit charges only the unshared tail, so the
      same bytes admit the redundant requests concurrently.

    Peak concurrent residents (and residents per DRAM GiB) is the
    capacity comparison; the two passes must agree token-for-token.
    A third, unconstrained pass submits requests one at a time so TTFT
    is pure admit-to-first-token: request 0 pays the cold prefill,
    every later request adopts the registered chain — prefix-hit TTFT
    vs cold-prefill TTFT without queueing noise."""
    backend = make_backend(backend_kind, model, params,
                           num_slots=concurrency, max_len=max_len,
                           mesh=mesh, block_tokens=block_tokens)
    hot_b, cold_b = backend.slot_kv_bytes()
    dram_budget = dram_budget_slots * hot_b
    rram = CapacityBudget.from_platform(CHIME).rram_bytes

    def fresh_engine(paged, budget=True, telemetry=None):
        sched = None
        if budget:
            sched = FCFSScheduler(
                CapacityBudget(dram_budget, rram), hot_b, cold_b,
                spill_lanes=backend.n_spill,
                lane_bytes=backend.spill_lane_bytes())
        return Engine(backend, scheduler=sched,
                      chunk_tokens=chunk_tokens,
                      token_budget=token_budget, paged=paged,
                      telemetry=telemetry)

    def stream(seed):
        return make_synthetic_requests(cfg, n_requests, prompt_len, gen,
                                       seed=seed, image_every=image_every,
                                       shared_prefix=shared)

    fresh_engine(True).run(stream(0))          # warm-up: pays compilation

    def drain(paged):
        engine = fresh_engine(paged)
        for r in stream(1):
            engine.submit(r)
        peak, step_s = 0, []
        t0 = time.perf_counter()
        while not engine.idle:
            ts = time.perf_counter()
            engine.step()
            step_s.append(time.perf_counter() - ts)
            peak = max(peak, engine.pool.active_slots)
        wall = time.perf_counter() - t0
        m = aggregate_metrics(engine.finished, wall)
        m["paged"] = paged
        m["peak_concurrency"] = peak
        m["requests_per_dram_gib"] = peak / (dram_budget / 2**30)
        m["steps"] = len(step_s)
        m["p50_step_s"] = float(np.percentile(step_s, 50))
        m["p95_step_s"] = float(np.percentile(step_s, 95))
        m["engine_stats"] = dict(engine.stats)
        m["endurance"] = engine.endurance_report()
        m["sim"] = simulated_efficiency(cfg, engine.finished)
        if engine.block_pool is not None:
            bp = engine.block_pool
            m["block_pool"] = {k: int(v) for k, v in bp.stats.items()
                               if k != "block_writes"}
            m["block_pool"]["peak_used_blocks"] = bp.used_blocks
        return m, {r.rid: list(r.generated) for r in engine.finished}

    slot_m, slot_toks = drain(False)
    paged_m, paged_toks = drain(True)
    parity = slot_toks == paged_toks

    # TTFT pass: unconstrained budget, one request in flight at a time,
    # so TTFT is admit-to-first-token with an empty queue. Request 0 is
    # the cold prefill that registers the chain; later requests hit it.
    eng = fresh_engine(True, budget=False)
    for r in stream(2):
        eng.submit(r)
        while not eng.idle:
            eng.step()
    seq = eng.finished[-n_requests:]
    cold = [r for r in seq if r.prefix_hit == 0]
    hits = [r for r in seq if r.prefix_hit > 0]
    cold_ttft = float(np.mean([r.first_token_s - r.arrival_s
                               for r in cold])) if cold else 0.0
    hit_ttft = float(np.mean([r.first_token_s - r.arrival_s
                              for r in hits])) if hits else 0.0

    return {
        "mode": "prefix-share",
        "shared_prefix": shared,
        "block_tokens": backend.block_tokens,
        "dram_budget_slots": dram_budget_slots,
        "dram_budget_bytes": dram_budget,
        "slot": slot_m,
        "paged": paged_m,
        "token_parity": parity,
        "capacity_gain": (paged_m["peak_concurrency"]
                          / max(slot_m["peak_concurrency"], 1)),
        "sequential_ttft": {
            "cold_requests": len(cold),
            "hit_requests": len(hits),
            "cold_mean_ttft_s": cold_ttft,
            "prefix_hit_mean_ttft_s": hit_ttft,
            "hit_faster": bool(hits) and hit_ttft < cold_ttft,
        },
    }


def append_bench_json(record: dict, path: pathlib.Path = BENCH_JSON):
    """Append one run record to the serving BENCH trajectory. Tolerates a
    truncated/corrupt file (starts fresh) and replaces atomically so an
    interrupted run can't wedge future ones."""
    path.parent.mkdir(parents=True, exist_ok=True)
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except json.JSONDecodeError:
            print(f"[bench] WARNING: {path} is corrupt; starting a "
                  f"fresh trajectory")
    history.append(record)
    tmp = path.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(history, indent=1, sort_keys=True) + "\n")
    tmp.replace(path)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced)")
    ap.add_argument("--backend", default="local",
                    choices=["local", "sharded"])
    ap.add_argument("--mesh", default="local",
                    help="sharded backend mesh (see launch.mesh.get_mesh)")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--requests", type=int, default=0,
                    help="requests per run (0 = 2x concurrency)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kv-policy", default="tiered",
                    choices=["flat", "tiered"])
    ap.add_argument("--hot-window", type=int, default=8)
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill chunk cap (0 = whole prompts "
                         "even under REPRO_SERVE_CHUNK_TOKENS; default: "
                         "consult the env knob)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget (0 = unbounded; "
                         "default: env knob / derived)")
    ap.add_argument("--image-every", type=int, default=0,
                    help="every k-th request is a VQA request (0 = none)")
    ap.add_argument("--priority-every", type=int, default=0,
                    help="every k-th request is priority-1 traffic")
    ap.add_argument("--oversubscribe", type=float, default=0.0,
                    help="> 1: compare an admission-blocked baseline "
                         "(DRAM budget = concurrency/F residents) "
                         "against spill-backed oversubscription at the "
                         "full slot count")
    ap.add_argument("--spill-compress", action="store_true", default=None,
                    help="int8-compress spill-lane hot rings; with "
                         "--oversubscribe > 1 this switches to the "
                         "capacity comparison: blocked baseline vs "
                         "full-precision lanes vs compressed lanes at "
                         "the SAME fixed DRAM + RRAM spill budgets "
                         "(lane count = budget // lane bytes)")
    ap.add_argument("--idle-offload-steps", type=int, default=None,
                    help="enable proactive idle cold-KV offload at this "
                         "residency threshold (see serving/scheduler.py)")
    ap.add_argument("--fused-decode", action="store_true", default=None,
                    help="fused Pallas paged-decode attention over the "
                         "tiered pool (GQA archs; default: consult "
                         "REPRO_SERVE_FUSED_DECODE)")
    ap.add_argument("--sparse-read", type=float, default=None,
                    metavar="TAU",
                    help="SLIM-style sparse-read threshold inside the "
                         "fused kernel (0 = exact; needs --fused-decode; "
                         "default: consult REPRO_SERVE_SPARSE_READ)")
    ap.add_argument("--weight-stream", type=int, default=None, metavar="W",
                    help="RRAM weight streaming: run the streamed-vs-"
                         "resident comparison at this DRAM sliding-"
                         "window depth (0 = off even under "
                         "REPRO_SERVE_WEIGHT_STREAM; default: consult "
                         "the env knob)")
    ap.add_argument("--prefix-share", type=int, default=0, metavar="N",
                    help="prefix-sharing comparison: every request opens "
                         "with the same N-token system prompt (and VQA "
                         "requests share one image); drains the stream "
                         "slot-charged vs block-charged at the same "
                         "clamped DRAM budget and measures peak "
                         "concurrency, hit rate and prefix-hit vs cold "
                         "TTFT (0 = off)")
    ap.add_argument("--block-tokens", type=int, default=None,
                    help="prefix-share page size in tokens (default: "
                         "backend's, i.e. ENDURANCE_BLOCK clamped to "
                         "max_len and the chunk grid)")
    ap.add_argument("--dram-budget-slots", type=int, default=0,
                    help="prefix-share DRAM budget, in worst-case slot "
                         "images (0 = concurrency // 2)")
    ap.add_argument("--no-json", action="store_true",
                    help="skip appending to the BENCH json trajectory")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=not args.full).replace(
        param_dtype="float32", compute_dtype="float32", remat="none",
        kv_policy=args.kv_policy, kv_hot_window=args.hot_window)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_requests = args.requests or 2 * args.concurrency
    vis = (cfg.frontend.num_tokens
           if args.image_every and cfg.frontend is not None else 0)
    max_len = max(args.prompt_len, vis + 1) + args.gen
    mesh = None
    if args.backend == "sharded":
        from repro.launch.mesh import get_mesh
        mesh = get_mesh(args.mesh)

    print(f"[bench] arch={args.arch} kv={args.kv_policy} "
          f"backend={args.backend} chunk={args.chunk_tokens or 0} "
          f"requests={n_requests} prompt={args.prompt_len} gen={args.gen}")

    def show(label, r):
        rep = r["endurance"]
        print(f"[bench] {label}: {r['tok_per_s']:8.1f} tok/s  "
              f"step p50={r['p50_step_s'] * 1e3:.1f}ms "
              f"p95={r['p95_step_s'] * 1e3:.1f}ms "
              f"decode p95={r.get('p95_decode_step_s', 0.0) * 1e3:.1f}ms  "
              f"ttft p95={r.get('ttft_p95_s', 0.0) * 1e3:.1f}ms "
              f"tbt p95={r.get('tbt_p95_s', 0.0) * 1e3:.1f}ms  "
              f"sim={r['sim']['sim_tokens_per_j']:.1f} tok/J  "
              f"endurance max writes/block="
              f"{rep['max_writes_per_cold_slot']:.2f} "
              f"({'OK' if rep['write_once_ok'] else 'VIOLATED'})")
        t = r["telemetry"]
        split = t["energy_split_j"]
        print(f"[bench]   ledger: dram={split.get('dram', 0.0):.3g} J "
              f"rram={split.get('rram', 0.0):.3g} J "
              f"compute={split.get('compute', 0.0):.3g} J "
              f"({'reconciles EXACT' if t['ledger_reconciles'] else 'DRIFT'}"
              f"; telemetry-on overhead {t['enabled_overhead_pct']:+.1f}%)")

    results = []
    if args.prefix_share:
        # prefix-sharing capacity comparison: same stream, same DRAM
        # budget, slot-charged vs block-charged admission (+ a
        # sequential pass for queue-free hit-vs-cold TTFT)
        base = args.dram_budget_slots or max(1, args.concurrency // 2)
        r = bench_prefix_share(
            model, params, cfg, args.backend, args.concurrency,
            n_requests, args.prompt_len, args.gen, max_len,
            args.prefix_share, base, mesh=mesh,
            chunk_tokens=args.chunk_tokens,
            token_budget=args.token_budget,
            image_every=args.image_every,
            block_tokens=args.block_tokens)
        results.append(r)
        sm, pm, tt = r["slot"], r["paged"], r["sequential_ttft"]
        print(f"[bench] shared prefix {args.prefix_share} tok, DRAM "
              f"budget {base} worst-case slots "
              f"({r['dram_budget_bytes']} B), block={r['block_tokens']}:")
        print(f"[bench]   slot-charged: peak {sm['peak_concurrency']} "
              f"concurrent ({sm['requests_per_dram_gib']:.1f}/GiB), "
              f"{sm['tok_per_s']:.1f} tok/s")
        print(f"[bench]   block-charged: peak {pm['peak_concurrency']} "
              f"concurrent ({pm['requests_per_dram_gib']:.1f}/GiB), "
              f"{pm['tok_per_s']:.1f} tok/s, hit rate "
              f"{pm.get('prefix_hit_rate', 0.0):.2f}, "
              f"{pm.get('block_pool', {}).get('cow_copies', 0)} CoW "
              f"(tokens {'MATCH' if r['token_parity'] else 'DIVERGE'})")
        print(f"[bench]   capacity x{r['capacity_gain']:.2f} at the same "
              f"DRAM budget; sequential TTFT: cold "
              f"{tt['cold_mean_ttft_s'] * 1e3:.1f} ms vs prefix-hit "
              f"{tt['prefix_hit_mean_ttft_s'] * 1e3:.1f} ms over "
              f"{tt['hit_requests']} hits "
              f"({'hit faster' if tt['hit_faster'] else 'NO SPEEDUP'})")
    elif args.oversubscribe and args.oversubscribe > 1 \
            and args.spill_compress:
        # CAPACITY comparison at fixed DRAM *and* RRAM spill budgets:
        # oversubscribed residents beyond the DRAM base must be backed
        # by spill lanes, and the lane count is what a fixed RRAM spill
        # budget divided by the lane bytes affords. The budget is sized
        # so int8-compressed lanes back the full overflow; full-
        # precision (PR 4) lanes afford fewer lanes from the SAME bytes,
        # so the baseline admits fewer residents — completed tok/s at
        # the full slot count is the comparison.
        from repro.serving import spill_lane_bytes as lane_bytes_of
        base = max(1, int(round(args.concurrency / args.oversubscribe)))
        overflow = args.concurrency - base
        full_b = lane_bytes_of(model, max_len, compressed=False)
        comp_b = lane_bytes_of(model, max_len, compressed=True)
        budget = overflow * comp_b
        lanes_full = int(budget // full_b)
        lanes_comp = int(budget // comp_b)
        print(f"[bench] RRAM spill budget {budget} B: "
              f"{lanes_full} full-precision lanes "
              f"({full_b} B) vs {lanes_comp} int8 lanes ({comp_b} B)")
        for label, compress, lanes in (
                ("blocked baseline", False, 0),
                (f"oversubscribe={args.oversubscribe:g} fp-lanes",
                 False, lanes_full),
                (f"oversubscribe={args.oversubscribe:g} int8-lanes",
                 True, lanes_comp)):
            r = bench_one(model, params, cfg, args.backend,
                          args.concurrency, n_requests, args.prompt_len,
                          args.gen, max_len, mesh=mesh,
                          chunk_tokens=args.chunk_tokens,
                          token_budget=args.token_budget,
                          image_every=args.image_every,
                          priority_every=args.priority_every,
                          dram_budget_slots=base,
                          oversubscribe=(1.0 if lanes == 0
                                         else args.oversubscribe),
                          n_spill=lanes, spill_compress=compress,
                          idle_offload_steps=args.idle_offload_steps,
                          rram_spill_bytes=budget)
            results.append(r)
            show(f"dram-budget={base} {label}", r)
        gain_fp = results[1]["tok_per_s"] / max(results[0]["tok_per_s"],
                                                1e-9)
        gain_int8 = results[2]["tok_per_s"] / max(results[0]["tok_per_s"],
                                                  1e-9)
        print(f"[bench] at a fixed DRAM budget of {base} residents and "
              f"{budget} B of spill RRAM: full-precision lanes buy "
              f"x{gain_fp:.2f}, int8 lanes x{gain_int8:.2f} completed "
              f"tok/s over the admission-blocked baseline")
    elif args.oversubscribe and args.oversubscribe > 1:
        # admission-blocked baseline vs spill-backed oversubscription at
        # the SAME tight DRAM budget (concurrency/F residents): the
        # oversubscribed engine reclaims the full slot count, the
        # baseline queues — completed-tokens/s is the comparison
        base = max(1, int(round(args.concurrency / args.oversubscribe)))
        for over in (1.0, args.oversubscribe):
            r = bench_one(model, params, cfg, args.backend,
                          args.concurrency, n_requests, args.prompt_len,
                          args.gen, max_len, mesh=mesh,
                          chunk_tokens=args.chunk_tokens,
                          token_budget=args.token_budget,
                          image_every=args.image_every,
                          priority_every=args.priority_every,
                          dram_budget_slots=base, oversubscribe=over,
                          idle_offload_steps=args.idle_offload_steps)
            results.append(r)
            show(f"dram-budget={base} oversubscribe={over:g}", r)
        speedup = results[1]["tok_per_s"] / max(results[0]["tok_per_s"],
                                                1e-9)
        print(f"[bench] oversubscription x{args.oversubscribe:g} buys "
              f"x{speedup:.2f} completed tok/s over the "
              f"admission-blocked baseline")
    elif args.weight_stream:
        # streamed-vs-resident weight comparison over the SAME stream at
        # the same slot count: resident weights are the parity oracle
        # (tokens must match exactly); the streamed run shrinks the DRAM
        # weight working set to embeddings + head + per-unit sliding
        # windows and pays the per-layer RRAM fetch energy in the sim
        for label, w in (("resident", 0),
                         (f"streamed W={args.weight_stream}",
                          args.weight_stream)):
            r = bench_one(model, params, cfg, args.backend,
                          args.concurrency, n_requests, args.prompt_len,
                          args.gen, max_len, mesh=mesh,
                          chunk_tokens=args.chunk_tokens,
                          token_budget=args.token_budget,
                          image_every=args.image_every,
                          priority_every=args.priority_every,
                          spill_compress=args.spill_compress,
                          idle_offload_steps=args.idle_offload_steps,
                          fused_decode=args.fused_decode,
                          sparse_read=args.sparse_read,
                          weight_stream=w)
            results.append(r)
            show(f"weights {label}", r)
        res, st = results
        print(f"[bench] weight streaming W={st['weight_stream']}: DRAM "
              f"weight working set {st['weight_bytes_dram']} B vs "
              f"resident {res['weight_bytes_dram']} B "
              f"({st['weight_bytes_rram']} B homed in RRAM); sim energy "
              f"{st['sim']['sim_energy_j']:.3f} J vs "
              f"{res['sim']['sim_energy_j']:.3f} J resident")
    else:
        for c in sorted({1, args.concurrency}):
            r = bench_one(model, params, cfg, args.backend, c, n_requests,
                          args.prompt_len, args.gen, max_len, mesh=mesh,
                          chunk_tokens=args.chunk_tokens,
                          token_budget=args.token_budget,
                          image_every=args.image_every,
                          priority_every=args.priority_every,
                          spill_compress=args.spill_compress,
                          idle_offload_steps=args.idle_offload_steps,
                          fused_decode=args.fused_decode,
                          sparse_read=args.sparse_read)
            results.append(r)
            show(f"concurrency={c:3d}", r)
        if len(results) == 2:
            speedup = results[1]["tok_per_s"] / max(
                results[0]["tok_per_s"], 1e-9)
            print(f"[bench] aggregate throughput x{speedup:.2f} at "
                  f"concurrency {args.concurrency} vs 1")
    if not args.no_json:
        append_bench_json({
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "arch": args.arch,
            "kv_policy": args.kv_policy,
            "hot_window": args.hot_window,
            "prompt_len": args.prompt_len,
            "gen": args.gen,
            "chunk_tokens": results[-1].get("chunk_tokens",
                                            args.chunk_tokens or 0),
            "image_every": args.image_every,
            "prefix_share": args.prefix_share or 0,
            "oversubscribe": args.oversubscribe or 0,
            "spill_compress": bool(args.spill_compress),
            "idle_offload_steps": args.idle_offload_steps or 0,
            "fused_decode": bool(args.fused_decode),
            "sparse_read": args.sparse_read or 0.0,
            "weight_stream": args.weight_stream or 0,
            "runs": results,
        })
        print(f"[bench] appended to {BENCH_JSON}")
    return results


if __name__ == "__main__":
    main()
