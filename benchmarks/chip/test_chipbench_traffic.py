"""The cells' traffic is fixed by their files: the seed draws only token
ids, image patches and weights."""

import json
import pathlib

import jax
import numpy as np
import pytest

import traffic as T
import weights as W

HERE = pathlib.Path(__file__).resolve().parent
TRAFFIC = sorted((HERE / "traffic").glob("*.json"))
SEEDS = (20_261_018, 3_000_000_011)


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_shapes_and_arrivals_do_not_depend_on_the_seed(path):
    a, b = T.load(path), T.load(path)
    assert a == b
    spec = json.loads(path.read_text())
    assert len(a.shapes) == spec["requests"]
    n = 3 * a.requests + 5        # past the end: the list cycles
    assert [a.offset_s(i) if a.loop == "open" else 0 for i in range(n)] \
        == [b.offset_s(i) if b.loop == "open" else 0 for i in range(n)]
    for s in a.shapes:
        lo, hi = spec["text_tokens"]["min"], spec["text_tokens"]["max"]
        assert lo <= s.text_len <= hi
        lo, hi = spec["output_tokens"]["min"], spec["output_tokens"]["max"]
        assert lo <= s.out_len <= hi
    # two seeds: same lengths, different ids and patches
    for i in (0, 1, a.requests - 1, a.requests + 1):
        s = a.shape(i)
        t1, p1 = T.request_arrays(SEEDS[0], i, s, 151_936, 64, 3072)
        t2, p2 = T.request_arrays(SEEDS[1], i, s, 151_936, 64, 3072)
        again, _ = T.request_arrays(SEEDS[0], i, s, 151_936, 64, 3072)
        assert t1.shape == t2.shape == (s.text_len,)
        assert (t1 == again).all() and not (t1 == t2).all()
        assert (p1 is None) == (p2 is None) == (not s.image)
        if s.image:
            assert p1.shape == p2.shape == (64, 3072)
            assert not np.allclose(p1, p2)


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_every_stretch_holds_the_whole_range(path):
    """Any 8 entries starting at a multiple of 8 hold one length from each
    eighth of the sorted list, for text and for output lengths."""
    tr = T.load(path)
    n, k = tr.requests, 8
    for key in ("text_len", "out_len"):
        vals = sorted(getattr(s, key) for s in tr.shapes)
        for start in range(0, n, k):
            block = sorted(getattr(s, key) for s in tr.shapes[start:start + k])
            for j, v in enumerate(block):
                assert vals[j * n // k] <= v <= vals[(j + 1) * n // k - 1]
    t = np.array([s.text_len for s in tr.shapes], float)
    o = np.array([s.out_len for s in tr.shapes], float)
    assert abs(np.corrcoef(t, o)[0, 1]) < 0.1


def test_quantile_lengths_hand_count():
    spec = {"min": 10, "max": 40, "median": 20, "sigma": 0.0}
    assert T.quantile_lengths(spec, 4) == [20, 20, 20, 20]
    spec = {"min": 15, "max": 25, "median": 20, "sigma": 1.0}
    # quantiles 1/8 and 7/8 lie at z = -/+1.15, e**1.15 = 3.16: clipped
    assert T.quantile_lengths(spec, 4) == [15, 15, 25, 25]


def test_bad_traffic_files_are_refused(tmp_path):
    base = {"loop": "closed", "clients": 1, "requests": 8,
            "text_tokens": {"min": 1, "max": 4, "median": 2, "sigma": 0.5},
            "output_tokens": {"min": 1, "max": 4, "median": 2,
                              "sigma": 0.5}}
    for bad in ({"requests": 6}, {"loop": "burst"}, {"clients": 0},
                {"loop": "open"}):
        p = tmp_path / "t.json"
        p.write_text(json.dumps({**base, **bad}))
        with pytest.raises(ValueError):
            T.load(p)


def test_weights_follow_the_seed_and_zero_the_padded_vocabulary():
    from repro.configs.base import get_config
    from repro.models import Model

    cfg = get_config("fastvlm-0.6b", reduced=True).replace(
        vocab_size=200, use_attn_bias=True)
    model = Model(cfg)                       # pads the vocabulary to 256
    a = W.make_params(model, SEEDS[0], 0.02, 200)
    b = W.make_params(model, SEEDS[0], 0.02, 200)
    c = W.make_params(model, SEEDS[1], 0.02, 200)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    table = np.asarray(a["embed"]["table"], np.float32)
    assert table.shape[0] == 256 and not table[200:].any()
    assert table[:200].std() == pytest.approx(0.02, rel=0.1)
    assert not np.array_equal(table, np.asarray(c["embed"]["table"],
                                                np.float32))
    assert not np.asarray(a["units"]["u0"]["mixer"]["bo"],
                          np.float32).any()
