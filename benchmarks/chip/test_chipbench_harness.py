"""The harness on the CPU at a tiny size: driven by data, refusing what it
cannot measure, and failing `correct` when the served path is broken.

The timed path here is the program's own (Engine, LocalBackend, the
fused decode kernel in interpret mode); only the chip checks of `main`
are skipped, by calling `harness.run_cell` directly.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import harness as H

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
# the loosest limit of every number any real cell compares
LIMITS: dict = {}
for _p in (HERE / "limits").glob("*.json"):
    for _k, _v in json.loads(_p.read_text()).items():
        LIMITS[_k] = max(LIMITS.get(_k, _v), _v)

# a tiny Qwen2-shaped configuration; its weights are larger than the
# published initializer range so that at two layers the next token
# depends on the context and not only on the last input token
TINY = {
    "name": "tiny-vlm", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 512, "rope_theta": 1e6,
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
# a new per-layer metric, added as a file and nothing else
NEW_READER = '''
def read(run):
    return float(sum(1 for r in run.due_in_window() if r.req.done))
'''


def make_root(tmp: pathlib.Path, limits: dict) -> tuple[dict, pathlib.Path]:
    """A benchmark tree of its own: a configuration, a traffic file, a
    limits file and the readers, each found by name."""
    root = tmp / "bench"
    (root / "traffic").mkdir(parents=True)
    (root / "limits").mkdir()
    shutil.copytree(HERE / "metrics", root / "metrics")
    shutil.copytree(HERE / "references", root / "references")
    (root / "metrics" / "done_requests.py").write_text(NEW_READER)
    (root / "tiny-vlm.json").write_text(json.dumps(TINY))
    (root / "traffic" / "tiny-chat.json").write_text(json.dumps(TRAFFIC))
    (root / "limits" / "tiny-vlm.tiny-chat.json").write_text(
        json.dumps(limits))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-vlm",
                         "file": str(root / "tiny-vlm.json")}]
    bench["workloads"] = [{"name": "tiny-vlm.tiny-chat",
                           "config": "tiny-vlm", "traffic": "tiny-chat",
                           "chips": 1}]
    bench["end_to_end"] = [
        {k: v for k, v in m.items() if k != "workloads"}
        for m in bench["end_to_end"]] + [
        {"name": "done_requests", "unit": "requests", "better": "higher",
         "bound": 0.25, "source": "host_clock"}]
    return bench, root


def run_tiny(tmp_path, seed=20_261_018):
    bench, root = make_root(tmp_path, LIMITS)
    cell = H.load_cell("tiny-vlm.tiny-chat", bench=bench, root=root)
    return H.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                      log=lambda s: None)


def test_files_found_by_name_and_every_request_runs_to_its_length(
        tmp_path):
    out = run_tiny(tmp_path)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert set(m) == {"output_tok_s", "ttft_p90_ms", "itl_p95_ms",
                      "setup_s", "done_requests"}
    assert m["done_requests"]["value"] == out["attempted"] > 0
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    assert out["checks"]["short_requests"]["value"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "cold_zero"])
def test_a_broken_decode_step_is_not_correct(tmp_path, monkeypatch, fault):
    """The decode step as the engine calls it, broken underneath: a token
    changed where it is produced, the KV state handed back unchanged (the
    new token's keys and values never stored), or the int8 cold tier's
    scales zeroed (the cold tier reads as nothing). The last two are the
    faults ``calibrate.py --faults`` plants at a cell's own size."""
    import calibrate
    from repro.serving import backend as B

    real = B._JittedBackend.decode_step

    def altered(self, toks, state, pos, active):
        ntoks, new_state = real(self, toks, state, pos, active)
        return (np.asarray(ntoks) + 1) % TINY["vocab_size"], new_state

    if fault == "token_altered":
        monkeypatch.setattr(B._JittedBackend, "decode_step", altered)
        out = run_tiny(tmp_path)
    else:
        with calibrate.planted(fault):
            out = run_tiny(tmp_path)
    assert B._JittedBackend.decode_step is real or fault == "token_altered"
    assert not out["correct"]
    assert any(out["checks"][k]["value"] > v for k, v in LIMITS.items())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(H.BenchError, match="no peaks"):
        H.peaks_for("TPU v99 imaginary")
    assert H.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH",)}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "fastvlm-0.6b.vqa-chat", "--seed", "7", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_a_cpu_platform_measures_nothing():
    p = _run(REPO)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not p.stdout.strip()


def test_outside_a_checkout_it_measures_nothing(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
