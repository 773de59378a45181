"""The one generator of every traffic mix.

A mix is a JSON file of parameters (``bench/traffic/<mix>.json``):

  loop            "closed": ``clients`` callers, each sending its next request
                  when the last one finished; "open": arrivals on a schedule
  slots           engine batch slots the server runs with
  rate_rps, cv    open loop: mean arrival rate and the coefficient of
                  variation of the gamma-distributed gaps (1 = Poisson)
  prompt_buckets  prompt lengths (tokens), drawn with ``prompt_weights``;
                  set-up warms exactly these lengths
  output          {"kind": "fixed", "tokens": n} or
                  {"kind": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  queue           closed loop: requests made ready for the callers
  shape_seed      seed of the schedule of sizes and gaps
  check_requests  finished requests compared with the reference per run

Every run seed gets the same work: the schedule of prompt lengths, output
lengths and gaps comes from ``shape_seed``, and the run's seed draws only
the prompt tokens.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    rid: int
    due_s: float  # open loop: when the request is due; closed loop: 0
    prompt: np.ndarray  # i32[P]
    max_new: int


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one run seed; any whole
    number is a valid seed."""
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), int(seed < 0), stream]))


def _counts(weights, n: int) -> list[int]:
    """Exact shares of ``n`` items for ``weights`` (largest remainder)."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _outputs(spec: dict, n: int, shape: np.random.Generator) -> np.ndarray:
    if spec["kind"] == "fixed":
        return np.full(n, int(spec["tokens"]), np.int64)
    if spec["kind"] == "lognormal":
        x = shape.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown output kind {spec['kind']!r}")


def max_output(mix: dict) -> int:
    spec = mix["output"]
    return int(spec["tokens"] if spec["kind"] == "fixed" else spec["max"])


def cache_rows(mix: dict, bs: int) -> int:
    """The smallest per-slot cache that holds the longest prompt, the longest
    output and the engine's ``2 * bs`` rows of verify and re-root headroom."""
    return max(mix["prompt_buckets"]) + max_output(mix) + 2 * bs


def n_requests(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_rps"] * seconds)))
    return int(mix["queue"])


def make_items(mix: dict, vocab: int, seed: int, seconds: float) -> list[Item]:
    """The run's requests, in the order they are due."""
    n = n_requests(mix, seconds)
    shape = np.random.default_rng(int(mix["shape_seed"]))
    order = rng(int(mix["shape_seed"]), 4)
    lens = np.repeat(mix["prompt_buckets"], _counts(mix["prompt_weights"], n))
    lens = order.permutation(lens)
    outs = order.permutation(_outputs(mix["output"], n, shape))
    if mix["loop"] == "open":
        cv = float(mix["cv"])
        k = 1.0 / (cv * cv)  # gamma shape for this coefficient of variation
        gaps = order.permutation(shape.gamma(k, 1.0 / k, n))
        # scale so the n arrivals fill [0, seconds) at exactly rate_rps
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    elif mix["loop"] == "closed":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    toks = rng(seed, 1)
    return [Item(rid=i, due_s=float(due[i]),
                 prompt=toks.integers(0, vocab, int(lens[i]), dtype=np.int32),
                 max_new=int(outs[i]))
            for i in range(n)]
