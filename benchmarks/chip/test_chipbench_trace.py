"""The trace reduction and the operation and byte counts, by hand."""

import pathlib

import pytest

import flops as F
import trace_reduce as TR

HERE = pathlib.Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "paged_decode_steps.xplane.pb"


def iv(name, s, e):
    return TR.Interval(name, float(s), float(e))


def synthetic() -> TR.TraceData:
    """A 100 ns window: two decode programs with a kernel inside each,
    one prefill program, host spans around the steps."""
    dev = "/device:TPU:0"
    ops = [iv("while.9 while", 10, 30), iv("fusion.1 fusion", 10, 20),
           iv("closed_call.5 custom-call mosaic", 15, 25),
           iv("copy.2 copy", 22, 30),
           iv("closed_call.5 custom-call mosaic", 52, 58),
           iv("fusion.3 fusion", 50, 60), iv("dot.4 dot", 80, 90)]
    mods = [iv("jit_step(17)", 10, 30), iv("jit_step(17)", 50, 60),
            iv("jit_ext_commit(9)", 80, 90)]
    spans = [iv("bench.traced", 0, 100), iv("bench.engine_step", 5, 35),
             iv("bench.engine_step", 45, 95), iv("bench.submit", 36, 44),
             iv("bench.backend.decode", 46, 62)]
    return TR.TraceData({dev: ops}, {dev: mods}, spans)


def test_busy_union_and_idle_gaps_by_hand():
    td = synthetic()
    dev = "/device:TPU:0"
    lo, hi = TR.window(td)
    assert (lo, hi) == (0.0, 100.0)
    # busy: [10, 30) + [50, 60) + [80, 90) = 40 ns
    assert TR.union(td.ops[dev], lo, hi) == [(10, 30), (50, 60), (80, 90)]
    assert TR.busy_ns(td.ops[dev], lo, hi) == 40.0
    assert TR.busy_ns(td.ops[dev], 15, 55) == 20.0
    gaps = TR.idle_gaps(td, dev, lo, hi, n=3)
    # gaps: [0,10) host, [30,50) submit (mid 40), [60,80) engine_step,
    # [90,100) engine_step; the three longest, longest first
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 20e-9, 10e-9])
    assert {g[0] for g in gaps[:2]} == {"bench.submit",
                                        "bench.engine_step"}
    assert gaps[2][0] in ("host", "bench.engine_step")


def test_modules_and_kernels_inside_them():
    td = synthetic()
    dev = "/device:TPU:0"
    runs = TR.module_runs(td, dev, "jit_step", 0, 100)
    assert [(m.start, m.end) for m in runs] == [(10, 30), (50, 60)]
    assert TR.module_runs(td, dev, "jit_ext_commit", 0, 100)[0].dur == 10
    inside = TR.ops_inside(td, dev, runs, lambda n: n.endswith("mosaic"))
    assert [[(o.start, o.end) for o in k] for k in inside] == \
        [[(15, 25)], [(52, 58)]]
    top = dict(TR.top_ops(td, 0, 100))
    assert top["closed_call.5 custom-call mosaic"] == pytest.approx(16e-9)
    assert "while.9 while" not in top          # a loop holds its body


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three calls of a jitted program
    named 'step' that runs the fused tiered decode kernel, each inside a
    'bench.engine_step' span and followed by a 3 ms 'bench.wait'."""
    td = TR.load(str(RECORDED))
    lo, hi = TR.window(td, "bench.window")
    devs = sorted(td.ops)
    assert devs and devs[0].startswith("/device:TPU:")
    dev = devs[0]
    runs = TR.module_runs(td, dev, "jit_step", lo, hi)
    assert len(runs) == 3
    # on the host's clock, each program runs inside its step's span
    steps = [s for s in td.spans if s.name == "bench.engine_step"]
    assert all(s.start < m.start < m.end < s.end
               for s, m in zip(steps, runs))
    kernels = TR.ops_inside(td, dev, runs,
                            lambda n: "paged_decode_tiered" in n)
    assert all(len(k) == 1 for k in kernels)
    busy = TR.busy_ns(td.ops[dev], lo, hi)
    assert 0 < busy < hi - lo
    gaps = TR.idle_gaps(td, dev, lo, hi, n=3)
    assert [g[0] for g in gaps] == ["bench.wait"] * 3
    assert all(2.9e-3 < g[1] < 0.05 for g in gaps)


SPEC = {"hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 3,
        "intermediate_size": 16, "vocab_size": 10, "mm_hidden_size": 6}


def test_short_op_names():
    assert TR.short_op("%fusion.3 = bf16[2]{0} fusion(bf16[2]{0} %p), "
                       "kind=kLoop") == "fusion.3 fusion"
    assert TR.short_op("%while.5 = (s32[], bf16[2,3]{1,0}) while((s32[], "
                       "bf16[2,3]{1,0}) %t), condition=%c") == \
        "while.5 while"
    assert TR.short_op('%closed_call.1 = bf16[1]{0} custom-call(s32[1]{0} '
                       '%a), custom_call_target="tpu_custom_call"') == \
        "closed_call.1 custom-call mosaic"
    assert TR.short_op("jit_step(12)") == "jit_step(12)"


def test_paged_decode_cost_by_hand():
    # D = 4, H = 2, Hkv = 1, hot window 4. Slot at 2 attends 3 hot keys;
    # slot at 9 attends 4 hot and 6 cold keys.
    ops, nbytes = F.paged_decode_tiered_cost(SPEC, [2, 9], 4)
    assert ops == 4 * 2 * 4 * 3 + 4 * 2 * 4 * 10
    hot = 2 * (3 + 4) * 1 * 4 * 2          # K and V, bf16
    cold = 2 * 6 * 1 * (4 + 4)             # int8 rows plus f32 scales
    qo = 2 * (2 * 2 * 4 * 2)               # query in, output out per slot
    assert nbytes == hot + cold + qo


def test_step_flops_by_hand():
    per_layer = 8 * 2 * 4 + 2 * 8 * 1 * 4 + 2 * 4 * 8 + 3 * 8 * 16
    assert F.linear_flops_per_row(SPEC) == 2 * 3 * per_layer
    assert F.attention_flops(SPEC, 5) == 4 * 3 * 2 * 4 * 5
    head = 2 * 8 * 10
    assert F.decode_flops(SPEC, 4) == 2 * 3 * per_layer + 4 * 3 * 2 * 4 * 5 \
        + head
    # 3 positions (1 image row): 1 + 2 + 3 = 6 causal pairs
    assert F.prefill_flops(SPEC, 3, 1) == 3 * 2 * 3 * per_layer \
        + 4 * 3 * 2 * 4 * 6 + 2 * (6 * 8 + 8 * 8) + head
