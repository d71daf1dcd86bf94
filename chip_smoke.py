"""Serve fastvlm-1.7b at full width on a TPU and check what comes out.

    python chip_smoke.py               # one chip: unfused, then fused decode
    python chip_smoke.py --four-chips  # four chips: sharded 2x2 vs local

fastvlm-1.7b (paper Table II: Qwen2-1.5B backbone, 28 layers, d_model
1536, 12 query / 2 KV heads of 128) is served at its published widths and
its config's bf16 dtypes, with random weights from a seed, through the
serving CLI's own entry point `repro.launch.serve.main`. The stream is 8
requests, half of them VQA (64 image tokens), 512 prompt positions and 32
new tokens each, on 4 decode slots. The hot ring holds the newest 128
positions in bf16 and the int8 cold tier holds the rest, so decode reads
both tiers.

One chip (the default) serves the stream twice: through the unfused
decode step, then with `--fused-decode`, whose compiled step must hold
the Pallas kernel (`tpu_custom_call`). `--four-chips` serves it on the
pjit `ShardedBackend` over a 2x2 mesh (slots over 'data', KV heads over
'model') and on the `LocalBackend` on device 0, in one process, and runs
nothing else.

Checks, each of which ends the script with a non-zero exit: a TPU is
present, every request finishes with its `max_new_tokens`, and every
emitted token of every request passes the teacher-forced check against
`Model.forward` (see `TOL_SIGMA` and `LOGIT_RTOL`). Numbers
printed on the way (compile seconds, tokens/s) are smoke figures of one
run, not a benchmark. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
STREAM = ["--arch", "fastvlm-1.7b", "--requests", "8", "--concurrency", "4",
          "--prompt-len", "512", "--gen", "32", "--image-every", "2",
          "--hot-window", "128"]
# Teacher-forced tolerance: each emitted token's reference logit must lie
# within TOL_SIGMA standard deviations (of that position's reference
# logits) of the reference maximum. Serving and reference both run in
# bf16, but they accumulate in different orders (one cached token at a
# time through the bf16 hot ring and the int8 cold tier, against one
# full-sequence pass), and the bf16 logits near the maximum are spaced
# about 1/40 of a standard deviation apart at these widths. With 152k
# vocabulary entries the maximum stands about 4.5 standard deviations
# above the mean, so a broken path lands far outside this band.
TOL_SIGMA = 0.1
# The emitted tokens alone are a weak check with random weights: the
# tied embedding makes each position favour a token fixed by its own
# input, so a request tends to repeat one token whatever attention
# returns. The logit rows of the cached decode path (bf16 hot ring, int8
# cold tier, the fused kernel where enabled) are therefore held to the
# reference too: their rms error must stay under LOGIT_RTOL standard
# deviations of the reference row.
LOGIT_RTOL = 0.05
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 2x2 vs local comparison "
                         "(needs four chips)")
    return ap.parse_args(argv)


class CompileClock:
    """Seconds spent in XLA compilation, persistent-cache reads included,
    and the number of programs read back from that cache, summed from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.hits = 0.0, 0
        self.total_secs, self.total_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.secs += secs
            self.total_secs += secs

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1
            self.total_hits += 1

    def lap(self) -> tuple[float, int]:
        lap = self.secs, self.hits
        self.secs, self.hits = 0.0, 0
        return lap


def fail(msg: str):
    print(f"[smoke] FAILED: {msg}", flush=True)
    raise SystemExit(1)


def serve(extra: list[str], clock: CompileClock, label: str):
    """One pass of the stream through the serving CLI; returns the
    finished requests and the backend that served them."""
    from repro.launch.serve import main as serve_main

    clock.lap()
    t0 = time.perf_counter()
    done, backend = serve_main(STREAM + extra)
    wall = time.perf_counter() - t0
    compile_s, hits = clock.lap()
    want = int(STREAM[STREAM.index("--requests") + 1])
    if len(done) != want:
        fail(f"{label}: {len(done)} of {want} requests finished")
    for r in done:
        if r.n_generated != r.max_new_tokens:
            fail(f"{label}: request {r.rid} emitted {r.n_generated} of "
                 f"{r.max_new_tokens} tokens")
    # steady window: from the first decode-step emission (all programs
    # compiled by then) to the last token
    t_first = min(r.token_times[1] for r in done)
    t_last = max(r.token_times[-1] for r in done)
    n_steady = sum(1 for r in done for t in r.token_times if t > t_first)
    print(f"[smoke] {label}: {len(done)} requests x {done[0].max_new_tokens}"
          f" tokens finished; wall {wall:.2f} s, compile {compile_s:.2f} s"
          f" ({hits} programs from the persistent cache);"
          f" steady {n_steady / (t_last - t_first):.1f} tok/s "
          f"(smoke figure, one run, not a benchmark)", flush=True)
    return done, backend


def cached_decode_logits(backend, r, batch, prefill, step):
    """Logit rows of request ``r`` through the cached decode path that
    the engine's step runs for each slot: prefill the prompt into the
    backend's KV store, then one `Model.decode_step` per generated token,
    fed the tokens the engine emitted."""
    import jax.numpy as jnp

    prompt = {k: v[:, :r.tokens.size] if k == "tokens" else v
              for k, v in batch.items()}
    logits, cache = prefill(backend.params, prompt, backend.max_len)
    rows = [logits[0, -1]]
    for i, tok in enumerate(r.generated[:-1]):
        logits, cache = step(backend.params,
                             jnp.full((1, 1), tok, jnp.int32), cache,
                             jnp.int32(r.prompt_len + i))
        rows.append(logits[0, -1])
    return jnp.stack(rows).astype(jnp.float32)


def teacher_forced(done, backend, label: str, ref=None):
    """Hold every request to the reference forward pass over prompt +
    generated tokens: each emitted token (TOL_SIGMA), and the logit rows
    of the cached decode path (LOGIT_RTOL). ``ref`` is the backend whose
    model and weights give the reference (default: the one that
    served)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = ref or backend
    fwd = jax.jit(ref.model.forward)
    prefill = jax.jit(backend.model.prefill, static_argnums=2)
    step = jax.jit(backend.model.decode_step)
    for r in sorted(done, key=lambda r: r.rid):
        gen = np.asarray(r.generated, np.int32)
        batch = {"tokens": jnp.asarray(
            np.concatenate([r.tokens, gen[:-1]])[None])}
        if r.patches is not None:
            batch["patches"] = jnp.asarray(r.patches[None])
        logits = fwd(ref.params, batch)[0].astype(jnp.float32)
        # row p predicts position p + 1: the first generated token comes
        # from the last prompt row
        rows = np.asarray(logits[r.prompt_len - 1:
                                 r.prompt_len - 1 + gen.size])
        sigma = rows.std(axis=1)
        gap = rows.max(axis=1) - rows[np.arange(gen.size), gen]
        worst = float((gap / sigma).max())
        agree = float((gap == 0).mean())
        served = np.asarray(
            cached_decode_logits(backend, r, batch, prefill, step))
        rel = float((np.sqrt(((served - rows) ** 2).mean(axis=1))
                     / sigma).max())
        kind = "VQA" if r.patches is not None else "text"
        ok = worst <= TOL_SIGMA and rel <= LOGIT_RTOL
        print(f"[smoke] {label}: teacher-forced request {r.rid} ({kind}): "
              f"worst token gap {worst:.4f} sigma (tolerance {TOL_SIGMA}),"
              f" reference argmax on {agree:.1%} of {gen.size} tokens, "
              f"{np.unique(gen).size} distinct; cached-decode logits rms "
              f"error {rel:.4f} sigma (tolerance {LOGIT_RTOL}): "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            fail(f"{label}: request {r.rid} left the teacher-forced band")


def decode_step_hlo(backend) -> str:
    """Optimized HLO of the backend's jitted decode step at the pool's
    shapes (the program the engine ran; jit's cache returns it)."""
    import jax
    import jax.numpy as jnp

    n = backend.num_slots
    cache, _ = backend.model.cache_spec(n, backend.max_len)
    vec = jax.ShapeDtypeStruct((n,), jnp.int32)
    return backend._step.lower(
        backend.params, jax.ShapeDtypeStruct((n, 1), jnp.int32), cache,
        vec, jax.ShapeDtypeStruct((n,), jnp.bool_)).compile().as_text()


def report_memory(dev, label: str):
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is None:
        fail("the device reports no peak_bytes_in_use")
    print(f"[smoke] {label}: peak device memory {peak / 2**30:.3f} GiB "
          f"({peak} B)", flush=True)


def one_chip(dev, clock):
    import jax

    done, backend = serve([], clock, "unfused decode")
    n_param = sum(x.nbytes for x in jax.tree.leaves(backend.params))
    print(f"[smoke] params: {n_param / 2**30:.3f} GiB ({n_param} B) in "
          f"{backend.model.cfg.param_dtype}", flush=True)
    teacher_forced(done, backend, "unfused decode")
    report_memory(dev, "unfused decode")
    # the backend and its jitted closures form reference cycles: collect
    # them now so the first phase's weights leave the device
    del done, backend
    gc.collect()

    done, backend = serve(["--fused-decode"], clock, "fused decode")
    if not backend.fused_decode:
        fail("--fused-decode did not resolve to the fused kernel")
    if "tpu_custom_call" not in decode_step_hlo(backend):
        fail("the fused decode step holds no tpu_custom_call: the Pallas "
             "kernel did not compile for the chip")
    print("[smoke] fused decode: compiled decode step holds "
          "tpu_custom_call: PASS", flush=True)
    teacher_forced(done, backend, "fused decode")
    report_memory(dev, "fused decode")


def four_chips(devs, clock):
    if len(devs) != 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    done_sh, sharded = serve(["--backend", "sharded", "--mesh", "2x2"],
                             clock, "sharded 2x2")
    done_lo, local = serve([], clock, "local device 0")
    lo = {r.rid: r.generated for r in done_lo}
    same = sum(a == b for r in done_sh
               for a, b in zip(r.generated, lo[r.rid]))
    total = sum(len(g) for g in lo.values())
    differ = sorted(r.rid for r in done_sh if r.generated != lo[r.rid])
    print(f"[smoke] sharded vs local: {same} of {total} tokens identical "
          f"({same / total:.1%}); requests that differ: {differ}",
          flush=True)
    # both streams against the LOCAL backend's reference forward, so a
    # token that differs between them passes only as a near tie
    teacher_forced(done_lo, local, "local device 0")
    teacher_forced(done_sh, sharded, "sharded 2x2", ref=local)
    for i, d in enumerate(devs):
        report_memory(d, f"device {i}")


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        print(f"[smoke] no repro package under {REPO / 'src'}: run this "
              f"script from a checkout of the repository", flush=True)
        return 2
    sys.path.insert(0, str(REPO / "src"))

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[smoke] no TPU: JAX's first device is "
              f"{devs[0].platform!r}; this check runs only on the chip",
              flush=True)
        return 1
    print(f"[smoke] device: {devs[0].device_kind} x {len(devs)} "
          f"(jax {jax.__version__}); compile cache {cache_dir}",
          flush=True)
    clock = CompileClock()
    if args.four_chips:
        four_chips(devs, clock)
    else:
        one_chip(devs[0], clock)
    print(f"[smoke] compile over the whole run: {clock.total_secs:.2f} s "
          f"({clock.total_hits} programs from the persistent cache)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
