"""The control, at a size a test run holds: the reference computed in int8
(W8A8) in the program's place fails the comparison that the program's
own bf16 tokens pass.

On the chip the control runs at each cell's own size
(``calibrate.py --control 1``); PERF.md gives those readings. Here the
model is a Qwen2-shaped one of 8 layers, d 128, whose limits were set the
same way from its own readings on the CPU: on seeds 1 to 4 the program's
mean gap over the sample read 0.0005 to 0.0016 sigma and the control's
0.0052 to 0.0139; their widest gaps 0.035 to 0.103 and 0.17 to 0.30.
"""

import json
import pathlib
import shutil

import pytest

import calibrate
import harness as H

HERE = pathlib.Path(__file__).resolve().parent
SMALL = {
    "name": "small-vlm", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 2048, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
    "initializer_range": 0.1, "torch_dtype": "bfloat16",
    "mm_hidden_size": 3072, "image_tokens": 64,
    "program_arch": "fastvlm-0.6b", "reference": "qwen2_vlm",
    "serving": {"slots": 2, "max_len": 128, "kv_policy": "tiered",
                "hot_window": 16, "cold_dtype": "int8",
                "fused_decode": True},
}
TRAFFIC = {"loop": "closed", "clients": 2, "requests": 16,
           "image_every": 2,
           "text_tokens": {"min": 8, "max": 40, "median": 16, "sigma": 0.5},
           "output_tokens": {"min": 4, "max": 12, "median": 8,
                             "sigma": 0.5}}
LIMITS = {"max_gap_sigma": 0.15, "mean_gap_sigma": 0.0025}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    (root / "traffic").mkdir()
    (root / "limits").mkdir()
    shutil.copytree(HERE / "references", root / "references")
    (root / "small.json").write_text(json.dumps(SMALL))
    (root / "traffic" / "chat.json").write_text(json.dumps(TRAFFIC))
    (root / "limits" / "small.chat.json").write_text(json.dumps(LIMITS))
    bench = {"configs": [{"name": "small", "file": str(root /
                                                       "small.json")}],
             "workloads": [{"name": "small.chat", "config": "small",
                            "traffic": "chat", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    return H.load_cell("small.chat", bench=bench, root=root)


@pytest.mark.parametrize("seed", [1, 2])
def test_the_int8_control_is_not_correct(cell, seed):
    row = calibrate.readings(cell, seed, 1.5, control=True)
    program = H.compare(cell.limits, row["program"])
    control = H.compare(cell.limits, row["control"])
    assert all(c["value"] <= c["limit"] for c in program.values()), program
    assert any(c["value"] > c["limit"] for c in control.values()), control
    assert row["control"]["checked_tokens"] == \
        row["program"]["checked_tokens"] > 0
