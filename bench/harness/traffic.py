"""The one traffic generator: a mix file's parameters and a seed ->
requests.

A mix (``bench/traffic/<name>.json``) states:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the system
  does) or ``"closed"`` (the queue is kept at ``queue`` requests, as an
  offline batch job keeps its server busy);
* ``rate_per_s`` and ``arrival_seed`` (open loop): mean arrival rate,
  and the seed of the one arrival schedule every run offers.  The
  window's inter-arrival gaps are the quantiles of the exponential
  distribution at that rate (a Poisson process's spread) in the order
  ``arrival_seed`` draws.  The run's seed draws everything else.  With
  ``bursts: {"on_s", "off_s"}`` arrivals come only in the on periods, at
  ``rate_per_s`` while on;
* ``request``: ``sampler``, ``steps``, ``guidance``, ``negative_prompt``
  (bool), and optionally ``preview_every`` and ``latent_hw`` (else the
  configuration's image size).  ``steps`` may be a list: each value
  gets an equal share of the requests.  ``classes``, a list of such
  dicts each with a ``weight``, replaces ``request`` for a mix of
  request kinds; every seed gets the same count of each, in another
  order;
* ``max_batch``: the server's batch bucket.

Prompts (and negative prompts) are distinct random token ids and each
request has its own noise seed, all drawn from the run's seed.
"""
from __future__ import annotations

import math

import numpy as np

from harness.weights import key_words


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(key_words(seed, stream))


def _shares(weights, n: int) -> list[int]:
    """``n`` split in proportion to ``weights`` (largest remainder)."""
    total = float(sum(weights))
    raw = [w * n / total for w in weights]
    out = [int(math.floor(x)) for x in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i])[
            :n - sum(out)]:
        out[i] += 1
    return out


def shapes(mix: dict, n: int, rng) -> list[dict]:
    """The request kind of each of ``n`` requests: the same multiset for
    every seed, in the seed's order."""
    classes = mix.get("classes") or [dict(mix["request"], weight=1)]
    kinds = []
    for cls, count in zip(classes, _shares([c["weight"] for c in classes],
                                           n)):
        steps = cls["steps"] if isinstance(cls["steps"], list) \
            else [cls["steps"]]
        for st, k in zip(steps, _shares([1] * len(steps), count)):
            kinds += [dict(cls, steps=int(st))] * k
    return [kinds[i] for i in rng.permutation(len(kinds))]


def requests(mix: dict, spec: dict, seed: int, n: int, *,
             stream: str = "window", rid0: int = 0) -> list[dict]:
    """``n`` requests of the mix, without arrival times."""
    rng = _rng(seed, stream)
    vocab = spec["text_encoder"]["vocab_size"]
    length = spec["text_encoder"]["max_position_embeddings"]
    out = []
    for i, rq in enumerate(shapes(mix, n, rng)):
        tokens = rng.integers(0, vocab, length).tolist()
        neg = (rng.integers(0, vocab, length).tolist()
               if rq["negative_prompt"] else None)
        out.append({"rid": rid0 + i, "tokens": tokens, "neg_tokens": neg,
                    "guidance": float(rq["guidance"]),
                    "sampler": rq["sampler"], "steps": rq["steps"],
                    "preview_every": int(rq.get("preview_every", 0)),
                    "seed": int(rng.integers(0, 2 ** 31 - 1)),
                    "latent_hw": int(rq.get("latent_hw",
                                            spec["latent_hw"]))})
    return out


def kind(r: dict) -> tuple:
    """What makes two requests' programs differ."""
    return (r["sampler"], r["steps"], r["latent_hw"], uses_cfg(r),
            r["preview_every"])


def arrivals(mix: dict, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop: ``rate``
    times the time arrivals are on, a little under the window."""
    rate = float(mix["rate_per_s"])
    bursts = mix.get("bursts")
    on, off = ((bursts["on_s"], bursts["off_s"]) if bursts
               else (seconds, 0.0))
    periods = int(seconds // (on + off))
    on_time = periods * on + min(on, seconds - periods * (on + off))
    n = int(math.floor(rate * on_time))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    t = np.cumsum(_rng(mix["arrival_seed"], "arrivals").permutation(gaps))
    return t + (t // on) * off


def uses_cfg(r: dict) -> bool:
    return r["neg_tokens"] is not None or r["guidance"] != 1.0
