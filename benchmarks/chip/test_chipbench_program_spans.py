"""The readers of the program's own spans, by hand, and on a trace that
the serving program recorded on a TPU v5e."""

import json
import pathlib
import shutil
import types

import pytest

import harness as H
import program_spans as PS
import trace_reduce as TR

HERE = pathlib.Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "paged_decode_steps.xplane.pb"
# three engine steps of fastvlm-1.7b.vqa-long with the program's spans
SPANS_RECORDED = HERE / "testdata" / "vqa_long_steps.xplane.pb"
DEV = "/device:TPU:0"
NEW = ("launch_idle_ms_per_step", "engine_idle_ms_per_step",
       "kv_live_share")
OLD = ("engine_host_ms_per_step", "decode_step_ms", "device_idle_share")


def iv(name, s, e):
    return TR.Interval(name, float(s), float(e))


def sp(name, s, e, **args):
    return PS.Span(name, float(s), float(e), args)


def synthetic():
    """A 200 ns window with two engine steps, each the benchmark's
    ``bench.engine_step`` span over the program's ``repro.engine.step``.

    Step 1 [10, 90): put [20, 25), launch [25, 40) of the decode program,
    which runs [38, 75); the host reads its tokens back [40, 80), and is
    busy on its own [80, 90).
    Step 2 [110, 190): a prefill put [115, 120) and launch [120, 130)
    whose program runs [128, 162); then the decode's put [150, 155) and
    launch [155, 160) while it still runs, the decode program
    [162, 185), and the read of its tokens [160, 186)."""
    ops = [iv("fusion.1 fusion", 38, 75), iv("fusion.2 fusion", 128, 162),
           iv("fusion.3 fusion", 162, 185)]
    mods = [iv("jit_step(1)", 38, 75), iv("jit_ext_commit(2)", 128, 162),
            iv("jit_step(1)", 162, 185)]
    bench = [iv("bench.traced", 0, 200), iv("bench.engine_step", 10, 90),
             iv("bench.engine_step", 110, 190)]
    spans = [
        sp("repro.engine.step", 10, 90, step_num=1),
        sp("repro.engine.plan", 11, 19, chunks=0, decode_slots=2),
        sp("repro.engine.decode", 19, 90, count=2),
        sp("repro.backend.step.put", 20, 25),
        sp("repro.backend.step.launch", 25, 40, kv_pool_bytes=1000,
           kv_live_bytes=100),
        sp("repro.engine.fetch", 40, 80),
        sp("repro.engine.emit", 80, 90, tokens=2),
        sp("repro.engine.step", 110, 190, step_num=2),
        sp("repro.engine.chunk", 112, 150, rid=7, start=0, length=8,
           commit=1),
        sp("repro.backend.ext_commit.put", 115, 120),
        sp("repro.backend.ext_commit.launch", 120, 130),
        sp("repro.engine.decode", 150, 190, count=3),
        sp("repro.backend.step.put", 150, 155),
        sp("repro.backend.step.launch", 155, 160, kv_pool_bytes=1000,
           kv_live_bytes=300),
        sp("repro.engine.fetch", 160, 186),
    ]
    return TR.TraceData({DEV: ops}, {DEV: mods}, bench), spans


def fake_run(td, spans=None):
    """What the readers read: a traced run of a cell; its program spans
    come from ``spans`` in place of the run's profile file."""
    run = types.SimpleNamespace(trace=td, device=DEV, peaks={},
                                cell=types.SimpleNamespace(name="x"))
    run.trace_window = lambda: TR.window(td)
    run.traced_steps = lambda: []
    run.program_spans = spans
    return run


@pytest.fixture
def readers(monkeypatch):
    monkeypatch.setattr(PS, "of_run", lambda run: run.program_spans)
    return {n: H.load_reader(n) for n in NEW + OLD}


def test_idle_split_by_hand():
    td, spans = synthetic()
    split = PS.idle_split(td, DEV, spans, 0, 200)
    # step 1: idle [10, 38) and [75, 90) = 43; inside put/launch/fetch
    #   [20, 38) and [75, 80)
    # step 2: idle [110, 128) and [185, 190) = 23; inside the spans
    #   [115, 128) and [185, 186); the decode's put and launch ran while
    #   the prefill program did, so they left the device no idle time
    assert split == [(23.0, 20.0), (14.0, 9.0)]


def test_new_readers_by_hand(readers):
    td, spans = synthetic()
    run = fake_run(td, spans)
    assert readers["launch_idle_ms_per_step"].read(run) == \
        pytest.approx((23 + 14) / 2 * 1e-6)
    assert readers["engine_idle_ms_per_step"].read(run) == \
        pytest.approx((20 + 9) / 2 * 1e-6)
    # (100 + 300) live bytes of (1000 + 1000)
    assert readers["kv_live_share"].read(run) == pytest.approx(20.0)


def test_launch_and_engine_idle_add_up_to_the_outside_host_time(readers):
    """Where ``bench.engine_step`` and ``repro.engine.step`` coincide,
    the two program-side shares sum to `engine_host_ms_per_step`."""
    td, spans = synthetic()
    run = fake_run(td, spans)
    host = readers["engine_host_ms_per_step"].read(run)
    split = readers["launch_idle_ms_per_step"].read(run) \
        + readers["engine_idle_ms_per_step"].read(run)
    assert split == pytest.approx(host)


def test_new_readers_are_silent_without_program_spans(readers):
    """A program without spans (as before they existed), and a run
    without a trace, give no value."""
    td, _ = synthetic()
    for name in NEW:
        assert readers[name].read(fake_run(td, None)) is None
        assert readers[name].read(fake_run(None, None)) is None


def test_program_steps_lie_in_the_benchmark_steps_of_the_recording():
    """`program_spans.load` and `trace_reduce.load` read one clock: in
    the chip recording, each of the program's ``repro.engine.step``
    spans lies inside one ``bench.engine_step`` span of the benchmark,
    which calls `Engine.step` once, and each of those holds one."""
    td = TR.load(str(SPANS_RECORDED))
    spans, window = PS.load(str(SPANS_RECORDED))
    lo, hi = window
    outer = [b for b in td.spans if b.name == "bench.engine_step"
             and lo <= b.start and b.end <= hi]
    steps = PS.inside(spans, PS.STEP, lo, hi)
    assert len(outer) == len(steps) >= 3
    for b, s in zip(outer, steps):
        assert b.start <= s.start < s.end <= b.end
        assert (s.start - b.start) + (b.end - s.end) < 0.05 * b.dur


def test_the_older_recording_has_no_program_spans():
    """The trace recorded before the program had spans: no program
    spans, so nothing for the new readers."""
    spans, window = PS.load(str(RECORDED))
    assert spans == [] and window is None
    td = TR.load(str(RECORDED))
    lo, hi = TR.window(td, "bench.window")
    assert PS.idle_split(td, sorted(td.ops)[0], spans, lo, hi) == []


def test_of_run_takes_only_the_runs_own_file(tmp_path, monkeypatch):
    """`of_run` reads the profile under the cell's trace directory, and
    only where that file's traced window is the run's."""
    dest = tmp_path / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    shutil.copy(SPANS_RECORDED, dest / "steps.xplane.pb")
    monkeypatch.setattr(PS, "trace_dir", lambda run: tmp_path)
    td = TR.load(str(SPANS_RECORDED))
    spans = PS.of_run(fake_run(td))
    assert spans and spans == PS.load(str(SPANS_RECORDED))[0]
    other = TR.TraceData(td.ops, td.modules,
                         [iv("bench.traced", 0, 1)])
    assert PS.of_run(fake_run(other)) is None
    assert PS.of_run(fake_run(None)) is None
    monkeypatch.setattr(PS, "trace_dir", lambda run: tmp_path / "none")
    assert PS.of_run(fake_run(td)) is None


def test_recorded_chip_steps_with_program_spans(readers):
    """Engine steps of fastvlm-1.7b.vqa-long recorded on a TPU v5e with
    the program's spans (`record_steps.py`): every step's phases and
    backend spans nest inside it, every decode program runs inside its
    launch's step, and the program's idle split adds up to the
    benchmark's outside reading of the same steps."""
    td = TR.load(str(SPANS_RECORDED))
    spans, window = PS.load(str(SPANS_RECORDED))
    assert window == TR.window(td)
    lo, hi = window
    dev = sorted(td.ops)[0]
    assert dev.startswith("/device:TPU:")
    steps = PS.inside(spans, PS.STEP, lo, hi)
    assert len(steps) >= 3
    for s in spans:
        if s.name != PS.STEP and lo <= s.start and s.end <= hi:
            assert any(t.start <= s.start and s.end <= t.end
                       for t in steps), s.name
    launches = PS.inside(spans, PS.DECODE_LAUNCH, lo, hi)
    runs = TR.module_runs(td, dev, "jit_step", lo, hi)
    assert launches and len(runs) >= len(launches) - 1
    for r in runs:
        assert any(t.start < r.start < t.end for t in steps)
    for s in launches:
        assert 0 < s.args["kv_live_bytes"] <= s.args["kv_pool_bytes"]
    split = PS.idle_split(td, dev, spans, lo, hi)
    assert all(a >= 0 and b >= 0 for a, b in split)
    run = fake_run(td, spans)
    run.device = dev
    got = {n: readers[n].read(run) for n in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    host = readers["engine_host_ms_per_step"].read(run)
    assert got["launch_idle_ms_per_step"] \
        + got["engine_idle_ms_per_step"] == pytest.approx(host, abs=0.3)
    # the idle gaps are named by the benchmark's spans, as before
    names = {g[0] for g in TR.idle_gaps(td, dev, lo, hi)}
    assert not any(n.startswith("repro.") for n in names)


def test_benchmark_lists_the_new_metrics():
    """The three metrics list no cells, so every cell, those added later
    too, reports them."""
    bench = json.loads((H.REPO / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["source"] == "program_span" and "workloads" not in m
        assert (HERE / "metrics" / f"{name}.py").is_file()
    for w in bench["workloads"]:
        got = {m["name"] for m in H.load_cell(w["name"], bench).per_layer}
        assert set(NEW) <= got, w["name"]


def test_strip_keeps_what_the_readers_read(tmp_path):
    """`record_steps.strip` rewrites a profile with no protobuf module:
    the program's and the benchmark's spans stay, with their args;
    other host events go."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    import record_steps as RS

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path / "trace")):
        with TraceAnnotation("bench.traced"):
            with TraceAnnotation("repro.engine.step", step_num=3):
                f(x).block_until_ready()
            with TraceAnnotation("other.span"):
                f(x).block_until_ready()
    src = pathlib.Path(TR.find_xplane(tmp_path / "trace"))
    dst = tmp_path / "stripped.xplane.pb"
    RS.strip(src, dst)
    assert dst.stat().st_size < src.stat().st_size
    spans, window = PS.load(str(dst))
    assert (spans, window) == PS.load(str(src))
    assert [(s.name, s.args) for s in spans] == \
        [("repro.engine.step", {"step_num": 3})]
    assert window is not None
    names = {e.name for p in ProfileData.from_file(str(dst)).planes
             for line in p.lines for e in line.events}
    assert "other.span" not in names and "bench.traced" in names
