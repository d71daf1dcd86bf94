"""Two-cut-point dataflow audit against real lowered HLO on a multi-device
mesh. Runs in a subprocess because the 8-device host platform must be
configured before jax initializes (the rest of the suite sees 1 device)."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax
from repro.configs.base import get_config
from repro.core.dataflow import count_collectives, lower_single_layer_hlo
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 8), ("data", "model"))
out = {}
for arch in ("granite-3-2b", "rwkv6-7b"):
    cfg = get_config(arch, reduced=True).replace(
        param_dtype="float32", compute_dtype="bfloat16")
    # widen so dims divide the 8-way model axis
    cfg = cfg.replace(d_model=128, d_ff=256, num_heads=8, num_kv_heads=8,
                      head_dim=16, vocab_size=256)
    hlo = lower_single_layer_hlo(cfg, mesh, batch=4, seq=32)
    out[arch] = count_collectives(hlo)
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_per_layer_collective_budget():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    res = json.loads(line[len("RESULT:"):])
    for arch, counts in res.items():
        total = sum(counts.values())
        # one-layer forward: the TP reductions at exactly the two cut
        # points (AttnOut partial-sum, FFNOut partial-sum) plus the
        # sharded-embedding gather. The CHIME fusion discipline means no
        # other collective fires inside a layer.
        assert total <= 5, (arch, counts)
        assert counts.get("all-reduce", 0) >= 2, (arch, counts)
