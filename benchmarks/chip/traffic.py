"""Seed-invariant request lists from a traffic file.

A traffic file (``traffic/<name>.json``) fixes the work of a cell:

    {"loop": "closed", "clients": 8,            # or "open" + "rate_per_s"
     "requests": 256,                           # length of the list (2**k)
     "image_every": 1,                          # every k-th request has an image
     "text_tokens":   {"min": ..., "max": ..., "median": ..., "sigma": ...},
     "output_tokens": {"min": ..., "max": ..., "median": ..., "sigma": ...}}

Lengths are lognormal values taken at the evenly spaced quantiles
(i + 0.5) / N, rounded and clipped to [min, max]. The sorted values are
dealt out in bit-reversed order of i for the text lengths and of
i ^ (i >> 3) for the output lengths: any 2**j entries starting at a
multiple of 2**j hold one value from each of 2**j equal strata of both
distributions, and the two lengths are all but uncorrelated. An open loop sends request i
at i / rate_per_s seconds. None of this depends on the seed: ``--seed``
draws only the token ids, the image patches and the weights.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics

import numpy as np

LOOPS = ("closed", "open")


@dataclasses.dataclass(frozen=True)
class Shape:
    """One entry of the request list: what a request asks of the server."""
    index: int
    text_len: int
    image: bool
    out_len: int


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    loop: str
    requests: int
    clients: int
    rate_per_s: float
    shapes: tuple[Shape, ...]

    def shape(self, i: int) -> Shape:
        """Entry ``i`` of the endless list: the fixed list, cycled."""
        return self.shapes[i % len(self.shapes)]

    def offset_s(self, i: int) -> float:
        """Open loop: seconds after the window opens at which request
        ``i`` is due."""
        return i / self.rate_per_s


def _bitrev(i: int, bits: int) -> int:
    return int(format(i, f"0{bits}b")[::-1], 2) if bits else 0


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """Sorted lognormal lengths at the quantiles (i + 0.5) / n, clipped."""
    lo, hi = int(spec["min"]), int(spec["max"])
    med, sigma = float(spec["median"]), float(spec["sigma"])
    if not (1 <= lo <= med <= hi) or sigma < 0:
        raise ValueError(f"bad length spec {spec}")
    nd = statistics.NormalDist()
    vals = [min(max(round(med * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))),
                    lo), hi) for i in range(n)]
    return sorted(vals)


def load(path: str | pathlib.Path, name: str | None = None) -> Traffic:
    path = pathlib.Path(path)
    d = json.loads(path.read_text())
    loop = d["loop"]
    if loop not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}, got {loop!r}")
    n = int(d["requests"])
    bits = n.bit_length() - 1
    if n < 1 or n != 1 << bits:
        raise ValueError(f"{path}: requests must be a power of two, got {n}")
    clients = int(d.get("clients", 0))
    rate = float(d.get("rate_per_s", 0.0))
    if loop == "closed" and clients < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if loop == "open" and rate <= 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    every = int(d.get("image_every", 0))
    text = quantile_lengths(d["text_tokens"], n)
    out = quantile_lengths(d["output_tokens"], n)
    shapes = tuple(
        Shape(index=i, text_len=text[_bitrev(i, bits)],
              image=bool(every) and i % every == 0,
              out_len=out[_bitrev(i ^ (i >> 3), bits)])
        for i in range(n))
    return Traffic(name=name or path.stem, loop=loop, requests=n,
                   clients=clients, rate_per_s=rate, shapes=shapes)


def request_rng(seed: int, index: int) -> np.random.Generator:
    """Per-request generator: request ``index`` of run ``seed`` draws the
    same ids and patches whenever it is made."""
    return np.random.default_rng([seed % 2 ** 64, index])


def request_arrays(seed: int, index: int, shape: Shape, vocab: int,
                   image_tokens: int, image_dim: int
                   ) -> tuple[np.ndarray, np.ndarray | None]:
    """(text token ids, image patch features or None) of one request.
    Ids are uniform over the model's vocabulary; patches are features of
    the stub vision encoder, uniform with zero mean and unit variance
    (cheaper to draw than normal ones), one image per request."""
    rng = request_rng(seed, index)
    toks = rng.integers(0, vocab, shape.text_len, dtype=np.int32)
    patches = None
    if shape.image:
        patches = (rng.random((image_tokens, image_dim), np.float32)
                   - np.float32(0.5)) * np.float32(math.sqrt(12.0))
    return toks, patches
