"""The ONE general traffic generator: it reads a mix file (`traffic/<mix>.json`)
and makes the inputs from the seed. A new mix is a new data file, never new
code. Everything here is a pure function of (mix, seed, vocabulary).

* `token_batches`: `queue` batches of `batch` x `seq` uniform token ids.
* `request_sizes`: the mix's FIXED multiset of (prompt length, output
  length) pairs, drawn once from the mix's own `sizes_seed`, clipped, and
  held to `prompt bucket + output <= max_seq_len`.
* `requests`: those same sizes IN THE SAME ORDER for every seed, with the
  prompt tokens drawn from the seed. Every seed therefore does the same
  work at the same moments; a first version drew the order from the seed
  too, and which long requests happened to complete inside the window then
  swung the completed tokens per second by 7% from seed to seed (my chip
  runs, PR 28).
"""

from __future__ import annotations

import math

import numpy as np


def token_batches(mix: dict, seed: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 1])
    return rng.integers(0, vocab, (int(mix["queue"]), int(mix["batch"]),
                                   int(mix["seq"]))).astype(np.int32)


def prompt_bucket(n: int, floor: int) -> int:
    """Smallest power of two >= n, at least `floor`: the ladder prompts pad to."""
    return max(int(floor), 1 << (int(n) - 1).bit_length())


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def request_sizes(mix: dict) -> np.ndarray:
    """(n_sizes, 2) int: prompt length, output length. The same for every
    seed of the run."""
    rng = np.random.default_rng(int(mix["sizes_seed"]))
    n = int(mix["n_sizes"])
    prompt = _lengths(rng, mix["prompt"], n)
    out = _lengths(rng, mix["output"], n)
    room = np.asarray([int(mix["max_seq_len"])
                       - prompt_bucket(p, mix["prompt_bucket_min"])
                       for p in prompt])
    out = np.minimum(out, room)
    if (out < mix["output"]["min"]).any():
        raise ValueError("a prompt's bucket leaves no room for the least output")
    return np.stack([prompt, out], 1)


def requests(mix: dict, seed: int, vocab: int) -> list:
    """[(prompt tokens int32 (p,), output length)], sizes in the mix's order."""
    rng = np.random.default_rng([seed, 2])
    return [(rng.integers(0, vocab, int(p)).astype(np.int32), int(o))
            for p, o in request_sizes(mix)]
