"""Shared serving-test fixtures and helpers.

The serving suites (tests/test_serving*.py) all need the same plumbing:
tiny float32 reduced-config models, reproducible request streams, a mesh
over whatever devices the process has, token-parity helpers against the
single-request `generate` oracle, and the forced-fake-device environment
for multi-device subprocess checks. It lives here ONCE; the test files
import the plain helpers (this directory is on sys.path both under
pytest's rootdir mode and when a test file runs as a script) or take the
pytest fixtures wrapping them.

`build_model` memoizes (arch, kv_policy, hot_window) -> (cfg, model,
params): params are functional and never mutated, so sharing one
initialization across every test in the session is a pure speedup.
"""

from __future__ import annotations

import functools
import os
import pathlib

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# tiny-model configs
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def build_model(arch: str = "granite-3-2b", kv_policy: str = "tiered",
                hot_window: int = 8):
    """(cfg, model, params) for a reduced float32 config — the shared
    serving-test model. Memoized per (arch, kv_policy, hot_window);
    treat the returned params as read-only (every repro op is
    functional, so they are)."""
    import jax

    from repro.configs.base import get_config
    from repro.models import Model

    cfg = get_config(arch, reduced=True).replace(
        param_dtype="float32", compute_dtype="float32", remat="none",
        kv_policy=kv_policy, kv_hot_window=hot_window)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# request streams
# ---------------------------------------------------------------------------
def make_requests(cfg, specs, seed: int = 0, priorities=None):
    """Reproducible text requests from (prompt_len, gen_len) ``specs``;
    ``priorities`` is an optional per-request priority list."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab_size, p)
                    .astype(np.int32),
                    max_new_tokens=g,
                    priority=0 if priorities is None else priorities[i])
            for i, (p, g) in enumerate(specs)]


def generated(done):
    """Token streams of finished requests in rid order — the shape every
    parity assertion compares."""
    return [r.generated for r in sorted(done, key=lambda r: r.rid)]


def oracle_tokens(model, params, req):
    """Single-request reference decode for ``req`` via `generate` (the
    sequential per-request oracle every engine run must match
    token-for-token)."""
    from repro.launch.serve import generate

    batch = {"tokens": req.tokens[None]}
    if req.patches is not None:
        batch["patches"] = req.patches[None]
    toks, _ = generate(model, params, batch, req.prompt_len,
                       req.max_new_tokens)
    return toks[0].tolist()


# ---------------------------------------------------------------------------
# device / mesh plumbing
# ---------------------------------------------------------------------------
def make_mesh():
    """Mesh over every visible device: (1, 1) locally; on a forced
    multi-device host platform, slots shard over 'data' and the cold
    kv_seq over 'model'."""
    import jax

    from repro.launch.mesh import make_local_mesh
    from repro.launch.mesh import make_mesh as _auto_mesh

    n = jax.device_count()
    if n == 1:
        return make_local_mesh()
    m = 2 if n % 2 == 0 else 1
    return _auto_mesh((n // m, m), ("data", "model"))


def forced_device_env(n: int = 8) -> dict:
    """Environment for a subprocess with ``n`` fake CPU devices (XLA
    flags must be set before jax initializes, so an in-process re-init
    is impossible)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


# ---------------------------------------------------------------------------
# fixture wrappers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="session")
def tiny_model():
    return build_model


@pytest.fixture
def request_factory():
    return make_requests


@pytest.fixture
def mesh_factory():
    return make_mesh
