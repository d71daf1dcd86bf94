"""The chip benchmark's harness: one cell, one seed, one measured window.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic file under
``traffic/``, its limits under ``limits/`` and one reader per metric
under ``metrics/``. Adding any of these is adding a file.

A run: make the weights on the device from the seed; build the
program's ``Model``, ``make_backend("local", ...)`` and ``Engine`` with
the configuration's serving options (everything else at the program's
defaults); warm up every prompt shape the traffic can send; for a closed
loop, run the clients until each has completed one request; then drive
``Engine.submit`` / ``Engine.step`` for the window; drain; read the
metrics; free the program's state; hold a seeded sample of the finished
requests to the reference. `setup_s` runs from process start to the
window's start.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
CLOCK = time.perf_counter
# the sample held to the reference: the longest finished request, then
# others drawn from the seed until this many served tokens are covered
CHECK_TOKENS = 300
DRAIN_STEPS = 100_000
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARMUP_INDEX = 2 ** 40          # request-array indices of warm-up requests
# a traced run profiles the last TRACE_S seconds of its window: a TPU
# trace of a serving step holds thousands of ops, and a longer one loses
# events (10 s of the 0.6b decode loop did) and takes long to read
TRACE_S = 2.0
SAMPLE_STREAM = 2 ** 41         # rng stream of the checked sample


class BenchError(RuntimeError):
    """A run that cannot produce a result (bad files, no chip)."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------
def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict              # the configuration file
    traffic: object         # traffic.Traffic
    limits: dict
    end_to_end: list        # BENCHMARK.json metric entries of this cell
    per_layer: list
    root: pathlib.Path = HERE   # where its traffic, limits, readers live


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None,
              root: pathlib.Path = HERE) -> Cell:
    import traffic as T

    bench = bench if bench is not None else load_json(REPO /
                                                      "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    spec = load_json(REPO / configs[w["config"]]["file"])
    return Cell(
        name=name, chips=int(w["chips"]), spec=spec,
        traffic=T.load(root / "traffic" / f"{w['traffic']}.json",
                       w["traffic"]),
        limits=load_json(root / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: pathlib.Path = HERE):
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for metric {name!r}")
    return _load_module(path, f"chip_metric_{name}")


def load_reference(spec: dict, root: pathlib.Path = HERE):
    name = spec["reference"]
    return _load_module(root / "references" / f"{name}.py",
                        f"chip_reference_{name}")


def peaks_for(kind: str, root: pathlib.Path = HERE) -> dict:
    table = load_json(root / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"peaks.json; have {sorted(table['devices'])}")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def program_config(spec: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture with every published size set from the file (the image's
    visual token count too), and the deployment's serving options."""
    from repro.configs.base import get_config

    d, H = spec["hidden_size"], spec["num_attention_heads"]
    sv = spec["serving"]
    cfg = get_config(spec["program_arch"]).replace(
        num_layers=spec["num_hidden_layers"], segments=(), d_model=d,
        num_heads=H,
        num_kv_heads=spec["num_key_value_heads"], head_dim=d // H,
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        rope_theta=float(spec["rope_theta"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        param_dtype=spec["torch_dtype"], compute_dtype=spec["torch_dtype"],
        remat="none", kv_policy=sv["kv_policy"],
        kv_hot_window=sv["hot_window"], kv_cold_dtype=sv["cold_dtype"],
        fused_decode=bool(sv["fused_decode"]))
    fe = cfg.frontend
    if fe is not None:
        fe = dataclasses.replace(fe, num_tokens=int(spec["image_tokens"]))
        cfg = cfg.replace(frontend=fe)
    want = (("mlp_type", "silu_gated"), ("norm_type", "rmsnorm"),
            ("pos_emb", "rope"), ("use_attn_bias", True),
            ("use_mlp_bias", False))
    bad = [k for k, v in want if getattr(cfg, k) != v]
    if fe is None or fe.frontend_dim != spec["mm_hidden_size"] \
            or fe.connector != "mlp":
        bad.append("frontend")
    if bad:
        raise BenchError(f"the program's {spec['program_arch']} differs "
                         f"from {spec['name']} in {bad}")
    return cfg


def image_rows(spec: dict, shape) -> int:
    return spec["image_tokens"] if shape.image else 0


@dataclasses.dataclass
class Record:
    """One request as the client saw it, with the program's Request."""
    index: int
    shape: object
    prompt_len: int
    image_rows: int
    due: float
    sent: float
    req: object
    decoded: int = 0         # tokens seen in step events so far


@dataclasses.dataclass
class Step:
    """One Engine.step in the window: its host span, the decode positions
    it wrote (one per decoded slot) and the prompts it committed."""
    start: float
    end: float
    decode_pos: list
    prefills: list           # (prompt_len, image_rows)


@dataclasses.dataclass
class Run:
    """What metric readers read."""
    cell: Cell
    peaks: dict
    chips: int
    seconds: float
    t0: float
    t1: float
    setup_s: float
    records: list
    steps: list
    hot_window: int
    trace: object = None     # trace_reduce.TraceData, or None
    device: str = ""         # its device plane
    traced_from: float = math.inf   # host clock at the traced span's start

    @property
    def spec(self) -> dict:
        return self.cell.spec

    @property
    def traffic(self):
        return self.cell.traffic

    def due_in_window(self) -> list:
        return [r for r in self.records if self.t0 <= r.due < self.t1]

    def traced_steps(self) -> list:
        """The engine steps inside the traced span."""
        return [s for s in self.steps if s.start >= self.traced_from]

    def trace_window(self) -> tuple[float, float]:
        import trace_reduce as TR
        return TR.window(self.trace)


class Harness:
    """Builds the program for one cell and seed and drives it."""

    def __init__(self, cell: Cell, seed: int, tracing: bool = False):
        import jax

        import weights as W
        from repro.models import Model
        from repro.serving import Engine, make_backend

        self.cell, self.seed, self.tracing = cell, seed, tracing
        spec = cell.spec
        self.spec = spec
        self.cfg = program_config(spec)
        self.model = Model(self.cfg)
        self.params = W.make_params(self.model, seed,
                                    spec["initializer_range"],
                                    spec["vocab_size"])
        jax.block_until_ready(self.params)
        sv = spec["serving"]
        self.backend = make_backend(
            "local", self.model, self.params, num_slots=sv["slots"],
            max_len=sv["max_len"], fused_decode=bool(sv["fused_decode"]))
        self.engine = Engine(self.backend)
        if tracing:
            self._span_backend()
        self.records: dict[int, Record] = {}
        self.steps: list[Step] = []
        self.traced_from = math.inf
        self.next_index = 0
        self.prepared: dict = {}
        # compilation times; the listener holds the list, not the harness
        compiles: list[float] = []

        def on_compile(event, secs, **_):
            if event == COMPILE_EVENT:
                compiles.append(CLOCK())

        self.compiles, self._on_compile = compiles, on_compile
        jax.monitoring.register_event_duration_secs_listener(on_compile)

    # -- spans ---------------------------------------------------------
    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def _span_backend(self):
        """Host spans around the calls into the backend (traced runs
        only): they name what the host was doing in each device gap."""
        b = self.backend
        for meth, name in (("decode_step", "backend.decode"),
                           ("extend_step", "backend.extend")):
            inner = getattr(b, meth)

            def wrapped(*a, _inner=inner, _name=name, **k):
                with self.span(_name):
                    return _inner(*a, **k)
            setattr(b, meth, wrapped)

    # -- requests ------------------------------------------------------
    def make_request(self, index: int, shape,
                     array_index: int | None = None):
        import traffic as T
        from repro.serving import Request

        spec = self.spec
        fe = self.cfg.frontend
        toks, patches = T.request_arrays(
            self.seed, index if array_index is None else array_index, shape,
            spec["vocab_size"], fe.num_tokens, fe.frontend_dim)
        return Request(rid=-1, tokens=toks, patches=patches,
                       max_new_tokens=shape.out_len)

    def prepare(self, seconds: float):
        """Make the requests the window will send before it opens: a
        client sends requests that already exist, so making their ids and
        patches is set-up, not serving. A closed loop gets the whole list,
        an open loop the arrivals due in ``seconds``; a run that needs
        more makes the rest as it sends them."""
        tr = self.cell.traffic
        n = tr.requests if tr.loop == "closed" else \
            self.next_index + math.ceil(tr.rate_per_s * seconds) + 1
        for i in range(self.next_index, n):
            self.prepared[i] = self.make_request(i, tr.shape(i))

    def submit(self, due: float | None = None) -> Record:
        """The client sends list entry ``next_index``."""
        i = self.next_index
        self.next_index += 1
        shape = self.cell.traffic.shape(i)
        req = self.prepared.pop(i, None)
        if req is None:
            with self.span("client"):
                req = self.make_request(i, shape)
        now = CLOCK()
        with self.span("submit"):
            self.engine.submit(req)
        rec = Record(index=i, shape=shape, prompt_len=req.prompt_len,
                     image_rows=image_rows(self.spec, shape),
                     due=now if due is None else due, sent=now, req=req)
        self.records[req.rid] = rec
        return rec

    def step(self, record: bool) -> list:
        """One Engine.step; returns the records that finished in it."""
        t = CLOCK()
        with self.span("engine_step"):
            events = self.engine.step()
        end = CLOCK()
        finished, decode_pos, prefills = [], [], []
        for rid, _tok, done in events:
            rec = self.records.get(rid)
            if rec is None:
                continue
            if rec.decoded == 0:
                prefills.append((rec.prompt_len, rec.image_rows))
            else:
                decode_pos.append(rec.prompt_len + rec.decoded - 1)
            rec.decoded += 1
            if done:
                finished.append(rec)
        if record:
            self.steps.append(Step(t, end, decode_pos, prefills))
        return finished

    # -- phases --------------------------------------------------------
    def warm_up(self):
        """Serve one short request per prompt-shape class the traffic can
        send: each image class at the shortest and longest text length
        and on both sides of every power of two in between, which covers
        every prefill program the engine builds for this traffic."""
        tr = self.cell.traffic
        lens: dict[bool, set] = {}
        for s in tr.shapes:
            lens.setdefault(s.image, set()).add(s.text_len)
        reqs = []
        for image, ls in sorted(lens.items()):
            lo, hi = min(ls), max(ls)
            want = {lo, hi}
            p = 1
            while p <= hi:
                want |= {x for x in (p, p + 1) if lo <= x <= hi}
                p *= 2
            for n in sorted(want):
                shape = dataclasses.replace(tr.shapes[0], text_len=n,
                                            image=image, out_len=2)
                reqs.append(self.make_request(
                    0, shape, array_index=WARMUP_INDEX + len(reqs)))
        self.engine.run(reqs, max_steps=DRAIN_STEPS)

    def prime(self):
        """Closed loop: start every client and run until as many requests
        have completed as there are clients, so that the window opens on
        a loop in flight rather than on its start-up burst."""
        tr = self.cell.traffic
        if tr.loop != "closed":
            return
        for _ in range(tr.clients):
            self.submit()
        done = 0
        while done < tr.clients:
            for _ in self.step(record=False):
                done += 1
                self.submit()

    def window(self, seconds: float, trace_dir=None
               ) -> tuple[float, float]:
        """Drive the traffic for ``seconds``; returns (t0, t1). With
        ``trace_dir``, the profiler records the window's last TRACE_S
        seconds, inside a ``bench.traced`` span."""
        tr = self.cell.traffic
        # set-up's objects leave the collector's view for the window, so
        # that no full collection over them pauses the loop
        gc.collect()
        gc.freeze()
        with contextlib.ExitStack() as stack:
            stack.callback(gc.unfreeze)
            t0 = CLOCK()
            t1 = t0 + seconds
            traced = trace_dir is None
            first = self.next_index
            while True:
                now = CLOCK()
                if now >= t1:
                    break
                if not traced and now >= t1 - TRACE_S:
                    import trace_reduce as TR
                    stack.enter_context(TR.capture(trace_dir))
                    stack.enter_context(self.span("traced"))
                    self.traced_from = CLOCK()
                    traced = True
                if tr.loop == "closed":
                    for _ in self.step(record=True):
                        self.submit()
                    continue
                while t0 + tr.offset_s(self.next_index - first) <= now:
                    self.submit(due=t0 + tr.offset_s(
                        self.next_index - first))
                if self.engine.idle:
                    nxt = min(t0 + tr.offset_s(self.next_index - first),
                              t1)
                    with self.span("wait"):
                        time.sleep(max(nxt - CLOCK(), 0.0))
                    continue
                self.step(record=True)
        return t0, t1

    def drain(self):
        """Serve every request already sent to its end; what does not
        finish counts as failed."""
        try:
            self.engine.run(max_steps=DRAIN_STEPS)
        except RuntimeError:
            pass

    def free_program(self):
        """Drop the engine and backend (their jitted closures sit in
        reference cycles) so that only the weights stay on the device."""
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        self.engine = self.backend = None
        gc.collect()


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------
def sample_for_check(records: list, seed: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until CHECK_TOKENS served tokens are covered."""
    done = [r for r in records if r.req.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.shape.out_len, r.prompt_len,
                                       -r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed % 2 ** 64, SAMPLE_STREAM])
    order = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [longest], len(longest.req.generated)
    for r in order:
        if n >= CHECK_TOKENS:
            break
        out.append(r)
        n += len(r.req.generated)
    return out


def token_gaps(ref, params, spec: dict, rec, control: bool = False
               ) -> np.ndarray:
    """Per served token: how far below the reference's best logit the
    token's own reference logit lies, in standard deviations of that
    row's reference logits. With ``control``, the token scored at each
    position is the one the int8 control puts first instead."""
    import jax.numpy as jnp

    req = rec.req
    served = np.asarray(req.generated, np.int64)
    ids = np.concatenate([req.tokens, served[:-1]]).astype(np.int32)
    start = req.prompt_len - 1
    rows = ref.logits(params, req.patches, ids, start)[:served.size]
    if control:
        ctl = ref.logits(params, req.patches, ids, start,
                         control=True)[:served.size]
        served = np.asarray(jnp.argmax(ctl, -1))
    vocab = spec["vocab_size"]
    inside = served < vocab
    picked = jnp.take_along_axis(
        rows, jnp.asarray(np.where(inside, served, 0))[:, None], 1)[:, 0]
    gap = (rows.max(-1) - picked) / rows.std(-1)
    return np.where(inside, np.asarray(gap, np.float64), np.inf)


def check(params, cell: Cell, records: list, seed: int,
          control: bool = False) -> dict:
    """{'max_gap_sigma', 'mean_gap_sigma', 'checked_tokens',
    'checked_requests'} over the seeded sample."""
    spec = cell.spec
    mod = load_reference(spec, cell.root)
    rows = max(s.out_len for s in cell.traffic.shapes)
    ref = mod.Reference(spec, spec["serving"]["max_len"] + rows, rows)
    sample = sample_for_check(records, seed)
    gaps = [token_gaps(ref, params, spec, r, control) for r in sample]
    allg = np.concatenate(gaps) if gaps else np.array([np.inf])
    return {"max_gap_sigma": float(allg.max()),
            "mean_gap_sigma": float(allg.mean()),
            "checked_tokens": int(sum(g.size for g in gaps)),
            "checked_requests": len(sample)}


def compare(limits: dict, got: dict) -> dict:
    """Each number the cell's limits file names, beside its limit."""
    return {k: {"value": got[k], "limit": v} for k, v in limits.items()}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def device_info(devs, chips: int) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(cell: Cell, seed: int, seconds: float, tracing: bool,
             t_process: float, devs=None, peaks: dict | None = None,
             trace_dir: pathlib.Path | None = None, log=print) -> dict:
    """One measured run of ``cell``; returns the result object. The chip
    checks live in `main`; tests call this directly on the CPU."""
    import jax

    import trace_reduce as TR

    devs = devs if devs is not None else jax.devices()
    t_build = CLOCK()
    h = Harness(cell, seed, tracing=tracing)
    t_warm = CLOCK()
    h.warm_up()
    h.prepare(seconds)
    t_prime = CLOCK()
    h.prime()
    n_compiles = len(h.compiles)
    setup_s = CLOCK() - t_process
    t0, t1 = h.window(seconds, trace_dir if tracing else None)
    in_window = sum(1 for c in h.compiles[n_compiles:] if c < t1)
    t_drain = CLOCK()
    h.drain()
    log(f"[bench] set-up {setup_s:.2f} s: imports {t_build - t_process:.2f}"
        f", weights and program {t_warm - t_build:.2f}, warm-up and "
        f"requests {t_prime - t_warm:.2f}, priming {t0 - t_prime:.2f}; drain "
        f"{CLOCK() - t_drain:.2f} s")
    device = device_info(devs, cell.chips)
    records = list(h.records.values())
    run = Run(cell=cell, peaks=peaks or {}, chips=cell.chips,
              seconds=seconds, t0=t0, t1=t1, setup_s=setup_s,
              records=records, steps=h.steps,
              hot_window=h.backend.hot_window, traced_from=h.traced_from)
    params = h.params
    h.free_program()

    breakdown = None
    if tracing:
        t_read = CLOCK()
        td = TR.load(TR.find_xplane(trace_dir))
        run.trace = td
        run.device = sorted(td.ops)[0] if td.ops else ""
        lo, hi = TR.window(td)
        devices = sorted(td.ops)[:cell.chips]
        busy = [TR.busy_ns(td.ops[d], lo, hi) for d in devices]
        device["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = {"device_ops": TR.top_ops(td, lo, hi),
                     "idle_gaps": TR.idle_gaps(td, run.device, lo, hi)}
        log(f"[bench] trace: {sum(map(len, td.ops.values()))} device ops "
            f"over {(hi - lo) * 1e-9:.2f} s, read in "
            f"{CLOCK() - t_read:.1f} s; ops span "
            f"{TR.coverage(td, run.device, lo, hi):.3f} of it")
    metrics = {}
    for m in (cell.per_layer if tracing else cell.end_to_end):
        value = load_reader(m["name"], cell.root).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    due = run.due_in_window()
    unfinished = sum(1 for r in due if not r.req.done)
    short = sum(1 for r in records if r.req.done
                and r.req.n_generated != r.shape.out_len)
    t_check = CLOCK()
    got = check(params, cell, due, seed)
    log(f"[bench] reference check {CLOCK() - t_check:.2f} s")
    checks = compare(cell.limits, got)
    checks["unfinished"] = {"value": unfinished, "limit": 0}
    checks["short_requests"] = {"value": short, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"[bench] {cell.name} seed {seed}: {len(due)} requests due in "
        f"{seconds} s window, {got['checked_requests']} checked over "
        f"{got['checked_tokens']} tokens; compilations in window: "
        f"{in_window}")
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": unfinished + short, "metrics": metrics,
           "device": device, "compiles_in_window": in_window}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def strip_program_env():
    """The deployment is what the configuration file says: no serving
    option may leak in from the environment."""
    for k in [k for k in os.environ if k.startswith("REPRO_SERVE_")]:
        del os.environ[k]


def enable_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR points), every program
    cached so that a second run compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv, t_process: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="one run of one benchmark "
                                 "cell on the chip")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    strip_program_env()
    if not (REPO / "src" / "repro").is_dir():
        print(f"[bench] no program under {REPO / 'src'}: run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    try:
        cell = load_cell(args.workload)
        import jax

        enable_compile_cache()
        devs = jax.devices()
        if devs[0].platform == "cpu":
            raise BenchError("JAX finds no accelerator; this benchmark "
                             "measures only on the chip")
        if len(devs) < cell.chips:
            raise BenchError(f"{args.workload} needs {cell.chips} chips, "
                             f"JAX finds {len(devs)}")
        peaks = peaks_for(devs[0].device_kind)
    except BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_process, devs=devs, peaks=peaks,
                   trace_dir=REPO / ".bench_trace" / args.workload,
                   log=lambda s: print(s, file=sys.stderr, flush=True))
    for name, c in out["checks"].items():
        print(f"[check] {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(f"compiles_in_window: {out['compiles_in_window']}", flush=True)
    print(json.dumps(out), flush=True)
    return 0
