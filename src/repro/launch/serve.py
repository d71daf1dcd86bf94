"""Serving CLI: a thin front-end over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch paligemma-3b \
        --reduced --requests 8 --concurrency 4 --prompt-len 32 --gen 16 \
        --kv-policy tiered

`generate` below is the single-request reference path (prefill + one
sequence of decode steps); the engine's per-slot decode is numerically
identical to it, and tests/test_serving.py holds the two to exact token
agreement.

Execution strategy is picked by --backend: 'local' runs the single-host
vmapped decode, 'sharded' runs the same step under pjit on a --mesh
(local | production | multipod | DxM) with params sharded by the model's
rules and the KV pool slots over 'data' / cold kv_seq over 'model'.
tests/test_serving_sharded.py holds the two backends to exact token
parity.

--chunk-tokens N enables Sarathi-style chunked prefill: prompts stream
into their pool slot in N-token chunks through the backend's unified
`extend_step`, so decode slots keep emitting between chunks instead of
stalling for a whole (vision) prompt. Chunked and whole-prompt prefill
are token-for-token identical (tests/test_serving_chunked.py).

--oversubscribe F relaxes the scheduler's DRAM admission gate by F
(spill-lane-backed; see serving/scheduler.py), and --priority-every K
marks every K-th request as priority-1 interactive traffic, submitted
mid-run so it preempts a running victim: the victim's KV slot spills to
an RRAM lane and restores bit-exactly (tests/test_serving_preempt.py
holds preempted == uninterrupted == generate()).

--idle-offload-steps N turns RRAM into a first-class capacity tier:
runners resident >= N decode steps proactively offload (bit-exact,
verbatim lanes) so blocked equal-priority waiters admit under the base
DRAM gate — no oversubscribe factor needed
(tests/test_serving_spill.py holds offloaded == uninterrupted ==
generate()). --spill-compress stores the lanes' hot ring int8-quantized
(a parked image costs ~the cold tier's bytes; restore is then
bounded-error rather than bit-exact — see the codec contract in
core/quant.py).

--trace-out / --metrics-out / --snapshots-out / --stats-every turn on
the serving telemetry hub (serving/telemetry.py): a Perfetto/Chrome
timeline of engine phases and per-slot/lane/request lifecycles, a
Prometheus text exposition of the counters/gauges/decision codes, and
periodic JSONL snapshots including the simulated per-tier traffic
ledger (which reconciles bit-for-bit with `simulated_efficiency` on a
drained run). Telemetry is off — a no-op null object — unless one of
these flags is given.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.models import Model


def generate(model: Model, params, batch: dict, prompt_len: int,
             gen_len: int, greedy: bool = True, rng=None):
    """Prefill then autoregressive decode; returns (tokens, cache)."""
    max_len = prompt_len + gen_len
    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, max_len))(params, batch)
    step = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    toks = [tok]
    for i in range(gen_len - 1):
        pos = jnp.asarray(prompt_len + i, jnp.int32)
        logits, cache = step(params, tok, cache, pos)
        if greedy or rng is None:
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        else:
            rng, k = jax.random.split(rng)
            tok = jax.random.categorical(
                k, logits[:, -1])[:, None].astype(jnp.int32)
        toks.append(tok)
    return jnp.concatenate(toks, axis=1), cache


def main(argv=None):
    """Serve a synthetic stream; returns (finished requests, backend)."""
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import get_mesh
    from repro.serving import (Engine, Telemetry, aggregate_metrics,
                               make_backend, make_synthetic_requests,
                               simulated_efficiency)

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paligemma-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--backend", default="local",
                    choices=["local", "sharded"],
                    help="executor: single-host vmapped decode or "
                         "pjit-sharded over --mesh")
    ap.add_argument("--mesh", default="local",
                    help="sharded backend mesh: local | production | "
                         "multipod | DxM (e.g. 4x2)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--concurrency", type=int, default=4,
                    help="decode slots (continuous-batching width)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kv-policy", default="tiered",
                    choices=["flat", "tiered"])
    ap.add_argument("--hot-window", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="KV pool length per slot (0 = prompt+gen)")
    ap.add_argument("--image-every", type=int, default=0,
                    help="every k-th request is a VQA request (0 = none)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="every request opens with the same N-token "
                         "system prompt (VQA requests also share one "
                         "image) — the stream shape --prefix-cache "
                         "exists for (0 = fully distinct prompts)")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill: cap a prefill chunk at this "
                         "many tokens (0 = whole-prompt chunks, even if "
                         "REPRO_SERVE_CHUNK_TOKENS is set; default: "
                         "consult the env knob)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget split between decode "
                         "slots and prefill chunks (0 = unbounded; "
                         "default: consult REPRO_SERVE_TOKEN_BUDGET, "
                         "else chunk+slots when chunking)")
    ap.add_argument("--oversubscribe", type=float, default=None,
                    help="relax the DRAM admission gate by this factor "
                         "(>= 1), spill-lane-backed (0 = off even under "
                         "REPRO_SERVE_OVERSUBSCRIBE; default: consult "
                         "the env knob)")
    ap.add_argument("--priority-every", type=int, default=0,
                    help="every k-th request is priority-1 interactive "
                         "traffic, submitted mid-run so it preempts "
                         "(0 = uniform priority)")
    ap.add_argument("--spill-lanes", type=int, default=None,
                    help="RRAM spill lanes for preempted slots "
                         "(default: one per decode slot; 0 disables "
                         "preemption)")
    ap.add_argument("--spill-compress", action="store_true", default=None,
                    help="int8-compress the hot ring in spill lanes "
                         "(bounded-error restore; a parked image then "
                         "costs ~the cold tier's RRAM bytes; default: "
                         "consult REPRO_SERVE_SPILL_COMPRESS)")
    ap.add_argument("--fused-decode", action="store_true", default=None,
                    help="fused Pallas paged-decode attention: stream "
                         "K/V pages straight from the tiered layout "
                         "with in-kernel int8 dequant (GQA archs only; "
                         "default: consult REPRO_SERVE_FUSED_DECODE)")
    ap.add_argument("--weight-stream", type=int, default=None,
                    metavar="W",
                    help="RRAM weight streaming: keep a W-repeat DRAM "
                         "sliding window per scanned unit and prefetch "
                         "the remaining per-layer weight slices from "
                         "RRAM one layer ahead inside the scan "
                         "(0 = off even under "
                         "REPRO_SERVE_WEIGHT_STREAM; default: consult "
                         "the env knob)")
    ap.add_argument("--sparse-read", type=float, default=None,
                    metavar="TAU",
                    help="SLIM-style adaptive-threshold sparse read "
                         "inside the fused kernel: skip cold pages whose "
                         "score upper bound is < running-max + log(TAU) "
                         "(0 = exact; needs --fused-decode; default: "
                         "consult REPRO_SERVE_SPARSE_READ)")
    ap.add_argument("--paged", action="store_true", default=None,
                    help="charge the admission gate per live KV block "
                         "instead of per worst-case slot (default: "
                         "consult REPRO_SERVE_PAGED; implies "
                         "--prefix-cache)")
    ap.add_argument("--prefix-cache", action="store_true", default=None,
                    help="hash-indexed prefix reuse over the paged pool: "
                         "admissions whose prompt head matches a cached "
                         "block chain skip prefill for the hit blocks "
                         "(default: on whenever paged)")
    ap.add_argument("--idle-offload-steps", type=int, default=None,
                    help="proactively offload a runner resident >= this "
                         "many decode steps to an RRAM lane when an "
                         "equal-or-higher-priority waiter is blocked "
                         "(0 = off even under "
                         "REPRO_SERVE_IDLE_OFFLOAD_STEPS; default: "
                         "consult the env knob)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON timeline "
                         "(open in ui.perfetto.dev) — enables telemetry")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus text exposition at the end "
                         "of the run — enables telemetry")
    ap.add_argument("--snapshots-out", default=None, metavar="PATH",
                    help="append JSONL telemetry snapshots every "
                         "--stats-every steps — enables telemetry")
    ap.add_argument("--stats-every", type=int, default=0,
                    help="print (and --snapshots-out: persist) a "
                         "telemetry snapshot every N engine steps "
                         "(0 = only at exit) — enables telemetry")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced).replace(
        remat="none", kv_policy=args.kv_policy,
        kv_hot_window=args.hot_window)
    if args.reduced:
        # the reduced configs' parity tests hold float32 token streams;
        # a full-width config serves in its own (bf16) dtypes
        cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    model = Model(cfg)
    # one compiled program: eager init compiles every op on its own and
    # keeps each float32 draw alive beside its cast
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # VQA requests occupy cfg.frontend.num_tokens visual positions on top
    # of (at least one) text token — size the pool for the larger of the
    # two request shapes
    vis = (cfg.frontend.num_tokens
           if args.image_every and cfg.frontend is not None else 0)
    max_len = args.max_len or (max(args.prompt_len, vis + 1) + args.gen)

    backend = make_backend(
        args.backend, model, params, num_slots=args.concurrency,
        max_len=max_len,
        mesh=get_mesh(args.mesh) if args.backend == "sharded" else None,
        n_spill=args.spill_lanes, spill_compress=args.spill_compress,
        fused_decode=args.fused_decode, sparse_read=args.sparse_read,
        weight_stream=args.weight_stream)
    # telemetry is opt-in: any of the export flags (or --stats-every)
    # turns the hub on; otherwise Engine installs the no-op NullTelemetry
    want_tel = (args.trace_out or args.metrics_out or args.snapshots_out
                or args.stats_every)
    tel = Telemetry(stats_every=args.stats_every,
                    snapshot_path=args.snapshots_out,
                    printer=print) if want_tel else None
    # pass through verbatim: None consults the env knobs, an explicit 0
    # disables (Engine treats 0 as the disable sentinel)
    engine = Engine(backend, chunk_tokens=args.chunk_tokens,
                    token_budget=args.token_budget,
                    oversubscribe=args.oversubscribe,
                    idle_offload_steps=args.idle_offload_steps,
                    paged=args.paged, prefix_cache=args.prefix_cache,
                    telemetry=tel)
    reqs = make_synthetic_requests(cfg, args.requests, args.prompt_len,
                                   args.gen, image_every=args.image_every,
                                   priority_every=args.priority_every,
                                   shared_prefix=args.shared_prefix)
    t0 = time.time()
    if args.priority_every:
        # interactive traffic lands mid-run: batch work first, then the
        # priority-1 requests once the slots are saturated — the
        # preemption path a single up-front submit would never take
        batch_reqs = [r for r in reqs if r.priority == 0]
        prio_reqs = [r for r in reqs if r.priority > 0]
        for r in batch_reqs:
            engine.submit(r)
        for _ in range(3):
            engine.step()
        engine.run(prio_reqs)
        done = engine.finished
    else:
        done = engine.run(reqs)
    wall = time.time() - t0

    m = aggregate_metrics(done, wall)
    print(f"[serve] arch={args.arch} kv={args.kv_policy} "
          f"backend={args.backend} "
          f"slots={args.concurrency}: {m['requests']} requests, "
          f"{m['total_tokens']} tokens in {wall:.2f}s "
          f"({m['tok_per_s']:.1f} tok/s incl. compile; "
          f"ttft p95 {m.get('ttft_p95_s', 0.0) * 1e3:.0f} ms, "
          f"tbt p95 {m.get('tbt_p95_s', 0.0) * 1e3:.0f} ms, "
          f"p95 latency {m.get('p95_latency_s', 0.0):.2f} s)")
    if args.chunk_tokens:
        s = engine.stats
        print(f"[serve] chunked prefill: {s['prefill_chunks']} chunks / "
              f"{s['extend_calls']} extend calls over {s['steps']} steps")
    if engine.stats["evictions"] or engine.stats["idle_offloads"]:
        lane_kind = ("int8-compressed" if backend.spill_compress
                     else "verbatim")
        print(f"[serve] spill ({lane_kind} lanes): "
              f"{engine.stats['evictions']} preemptions / "
              f"{engine.stats['idle_offloads']} idle offloads / "
              f"{engine.stats['restores']} restores "
              f"(restore latency p95 "
              f"{m.get('restore_latency_p95_s', 0.0) * 1e3:.1f} ms)")
    if engine.block_pool is not None:
        bp = engine.block_pool
        print(f"[serve] prefix cache: {m.get('prefix_hits', 0)} hits / "
              f"{m.get('prefix_hit_tokens', 0)} tokens skipped "
              f"(hit rate {m.get('prefix_hit_rate', 0.0):.2f}); "
              f"{bp.stats['cow_copies']} CoW copies, "
              f"{bp.used_blocks}/{bp.num_blocks} blocks live")
    if args.kv_policy == "tiered":
        rep = engine.endurance_report()
        print(f"[serve] endurance: max writes/cold-slot="
              f"{rep['max_writes_per_cold_slot']:.2f} "
              f"(write-once {'OK' if rep['write_once_ok'] else 'VIOLATED'})")
    # price with the backend's RESOLVED cfg (weight_stream_layers /
    # fused_decode baked in): the per-layer "streamed" flags the weight-
    # stream pricing keys off live in cost_layers(cfg)
    sim_cfg, _ = backend.sim_context()
    sim = simulated_efficiency(sim_cfg, done,
                               spill_compressed=backend.spill_compress,
                               fused_decode=backend.fused_decode,
                               sparse_read_tau=backend.sparse_read_tau,
                               weight_stream=bool(backend.weight_stream))
    fused_note = ""
    if backend.fused_decode:
        fused_note = " [fused decode" + (
            f", sparse tau={backend.sparse_read_tau:g}]"
            if backend.sparse_read_tau else "]")
    if backend.weight_stream:
        dram_w, rram_w = backend.weight_bytes()
        fused_note += (f" [weight stream W={backend.weight_stream}: "
                       f"{dram_w / 2**20:.1f} MiB DRAM working set, "
                       f"{rram_w / 2**20:.1f} MiB streamed from RRAM]")
    print(f"[serve] simulated on {sim['platform']}: "
          f"{sim['sim_tokens_per_j']:.1f} tok/J, "
          f"{sim['sim_energy_j']:.3f} J total{fused_note}")
    if tel is not None:
        if args.trace_out:
            tel.write_chrome_trace(args.trace_out)
            print(f"[serve] telemetry: Perfetto trace -> {args.trace_out}")
        if args.metrics_out:
            tel.write_prometheus(args.metrics_out)
            print(f"[serve] telemetry: Prometheus exposition -> "
                  f"{args.metrics_out}")
        if args.snapshots_out:
            print(f"[serve] telemetry: JSONL snapshots -> "
                  f"{args.snapshots_out}")
        led = tel.ledger.totals() if tel.ledger is not None else {}
        if led:
            split = led["sim_energy_split_j"]
            drift = abs(led["sim_energy_j"] - sim["sim_energy_j"])
            print(f"[serve] ledger: dram={split.get('dram', 0.0):.4g} J "
                  f"rram={split.get('rram', 0.0):.4g} J "
                  f"compute={split.get('compute', 0.0):.4g} J "
                  f"(reconciles with simulated_efficiency: "
                  f"{'EXACT' if drift == 0.0 else f'drift {drift:.3g} J'})")
        tel.close()
    print("[serve] sample token ids:", done[0].generated[:12])
    return done, backend


if __name__ == "__main__":
    main()
