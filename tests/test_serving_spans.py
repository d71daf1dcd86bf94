"""The engine's and backend's spans on the profiler's clock.

A few VQA requests of a small FastVLM are served while `jax.profiler`
records; the `.xplane.pb` it writes is read back with
`jax.profiler.ProfileData`. The spans must nest as the engine runs them
(every phase inside its `repro.engine.step`, every backend copy and
launch inside a decode or a prefill chunk), carry the request's id and
the KV pool's counters, and change no served token. The backend's
programs must keep the module names the benchmark's trace readers select
by.
"""

import glob
import re

import jax
import numpy as np
import pytest
from conftest import build_model

from repro.serving import Engine, LocalBackend, Request, slot_kv_bytes
from repro.serving.kv_pool import KVByteRates

jax.config.update("jax_platform_name", "cpu")

ARCH = "fastvlm-0.6b"
SLOTS = 2


def _requests(cfg, n=3, seed=7):
    rng = np.random.default_rng(seed)
    fe = cfg.frontend
    out = []
    for i in range(n):
        patches = None
        if i % 2 == 0:
            patches = rng.standard_normal(
                (fe.num_tokens, fe.frontend_dim)).astype(np.float32)
        toks = rng.integers(0, cfg.vocab_size, 6 + 3 * i).astype(np.int32)
        out.append(Request(rid=i, tokens=toks, patches=patches,
                           max_new_tokens=4 + i))
    return out


def _engine():
    cfg, model, params = build_model(ARCH)
    max_len = cfg.frontend.num_tokens + 24
    backend = LocalBackend(model, params, SLOTS, max_len)
    return cfg, backend, Engine(backend, chunk_tokens=8)


def _host_spans(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every ``repro.`` host event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Tokens served without the profiler, then the same requests served
    again under it, and the spans it recorded."""
    cfg, _, eng = _engine()
    plain = {r.rid: r.generated for r in eng.run(_requests(cfg))}
    cfg, backend, eng = _engine()
    reqs = _requests(cfg)
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        traced = {r.rid: r.generated for r in eng.run(reqs)}
    return plain, traced, backend, _host_spans(trace_dir)


def _inside(inner, outers) -> bool:
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_served_tokens_do_not_change_under_the_profiler(served):
    plain, traced, _, _ = served
    assert traced == plain and len(plain) == 3


def test_every_phase_lies_inside_a_step(served):
    spans = served[3]
    steps = [s for s in spans if s[0] == "repro.engine.step"]
    assert steps and all("step_num" in s[3] for s in steps)
    engine = [s for s in spans if s[0].startswith("repro.engine.")
              and s[0] != "repro.engine.step"]
    names = {s[0] for s in engine}
    assert {"repro.engine.plan", "repro.engine.chunk-prefill",
            "repro.engine.chunk", "repro.engine.commit",
            "repro.engine.decode", "repro.engine.fetch",
            "repro.engine.emit"} <= names
    # nothing was evicted or restored, so those phases never opened
    assert not names & {"repro.engine.evict", "repro.engine.idle-offload",
                        "repro.engine.restore"}
    assert all(_inside(s, steps) for s in engine)


def test_backend_spans_lie_inside_a_decode_or_a_chunk(served):
    spans = served[3]
    outer = [s for s in spans
             if s[0] in ("repro.engine.decode", "repro.engine.chunk")]
    backend = [s for s in spans if s[0].startswith("repro.backend.")]
    names = {s[0] for s in backend}
    for program in ("step", "ext_part", "ext_commit"):
        assert {f"repro.backend.{program}.put",
                f"repro.backend.{program}.launch"} <= names, names
    assert all(_inside(s, outer) for s in backend)


def test_chunk_and_commit_spans_carry_the_request(served):
    spans = served[3]
    chunks = [s for s in spans if s[0] == "repro.engine.chunk"]
    commits = [s for s in spans if s[0] == "repro.engine.commit"]
    assert {s[3]["rid"] for s in chunks} == {0, 1, 2}
    assert {s[3]["rid"] for s in commits} == {0, 1, 2}
    assert all({"start", "length", "commit"} <= set(s[3]) for s in chunks)
    # each request commits once, in a chunk of its own
    assert sum(s[3]["commit"] for s in chunks) == 3
    for c in commits:
        assert any(_inside(c, [k]) and k[3]["rid"] == c[3]["rid"]
                   for k in chunks)


def test_decode_launch_carries_the_pool_counters(served):
    backend, spans = served[2], served[3]
    launches = [s for s in spans if s[0] == "repro.backend.step.launch"]
    assert launches
    for s in launches:
        assert s[3]["kv_pool_bytes"] == backend.kv_pool_bytes
        assert 0 < s[3]["kv_live_bytes"] <= s[3]["kv_pool_bytes"]
    # emit counts the tokens the decode step produced
    emits = [s for s in spans if s[0] == "repro.engine.emit"]
    assert len(emits) == len(launches)
    assert all(1 <= s[3]["tokens"] <= SLOTS for s in emits)


def test_pool_and_live_bytes_by_hand():
    """The pool is every slot at max_len; a live slot at position p holds
    what `slot_kv_bytes` charges for p positions without page rounding."""
    cfg, model, params = build_model(ARCH)
    max_len = 40
    backend = LocalBackend(model, params, 3, max_len)
    hot, cold = slot_kv_bytes(model, max_len)
    assert backend.kv_pool_bytes == 3 * (hot + cold)
    rates = KVByteRates.of(model, max_len)
    pos = np.array([5, 30, 17], np.int32)
    active = np.array([True, True, False])
    want = sum(sum(slot_kv_bytes(model, max_len, length=p, block_tokens=1))
               for p in (5, 30))
    assert rates.live(pos, active) == want
    assert rates.live(pos, np.zeros(3, bool)) == 0
    # the hot ring holds at most its window; the cold tier every position
    assert rates.hot_cap == cfg.kv_hot_window < 30
    assert rates.cold > 0


def test_backend_programs_keep_their_module_names():
    """The benchmark reads its decode and prefill times by the XLA
    module names `jit_step`, `jit_ext_part` and `jit_ext_commit`."""
    cfg, backend, eng = _engine()
    pool = backend.init_pool()
    n = backend.num_slots
    toks = np.zeros((n, 1), np.int32)
    pos = np.zeros((n,), np.int32)
    lowered = {
        "step": backend._step.lower(backend.params, toks, pool.cache, pos,
                                    np.zeros((n,), bool)),
        "ext_part": backend._ext_part.lower(
            backend.params, {"tokens": np.zeros((1, 8), np.int32)},
            backend.fresh_extend(), np.int32(0), np.int32(8)),
        "ext_commit": backend._ext_commit.lower(
            backend.params, {"tokens": np.zeros((1, 8), np.int32)},
            pool.cache, backend.fresh_extend(), np.int32(0), np.int32(0),
            np.int32(8)),
    }
    for name, low in lowered.items():
        assert re.search(rf"module @jit_{name}\b", low.as_text()), name
