"""One general generator for every traffic mix.  A mix is a data file of
parameters; nothing here knows a cell by name.

Every seed gets the same set of sizes in another order: a length
distribution is a fixed grid of quantiles (``levels`` of them), the
requests are the cartesian product of the prompt and output grids, and
the seed only permutes that cycle and draws the token ids.  So two seeds
offer the same work, and a run's spread is the system's, not the draw's."""

from __future__ import annotations

import math

import numpy as np


def seed31(seed: int) -> int:
    """The driver's seeds pass 2**31; keys and numpy streams take 31 bits."""
    return int(seed) % (2**31 - 1)


def length_grid(spec: dict) -> list[int]:
    """The quantile midpoints of a length distribution.

    ``{"dist": "fixed", "value": n}``;
    ``{"dist": "log_uniform", "min": a, "max": b, "levels": k}``;
    ``{"dist": "log_normal", "median": m, "sigma": s, "min": a, "max": b,
    "levels": k}`` (clipped); ``{"dist": "choices", "values": [...]}``."""
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["value"])]
    if dist == "choices":
        return [int(v) for v in spec["values"]]
    k = int(spec["levels"])
    qs = [(i + 0.5) / k for i in range(k)]
    lo, hi = float(spec["min"]), float(spec["max"])
    if dist == "log_uniform":
        return [int(round(lo * (hi / lo) ** q)) for q in qs]
    if dist == "log_normal":
        from statistics import NormalDist

        nd = NormalDist(math.log(spec["median"]), float(spec["sigma"]))
        return [int(round(min(hi, max(lo, math.exp(nd.inv_cdf(q)))))) for q in qs]
    raise ValueError(f"unknown length distribution {dist!r}")


class RequestStream:
    """Endless, deterministic requests for a serving mix: ``next()`` gives
    ``{"prompt": int32 array, "max_new_tokens": n, "temperature": t}``."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        prompts = length_grid(mix["prompt_len"])
        outputs = length_grid(mix["output_len"])
        self.cycle = [(p, o) for p in prompts for o in outputs]
        self.rs = np.random.RandomState(seed31(seed))
        self.order = self.rs.permutation(len(self.cycle))
        self.vocab = int(vocab_size)
        self.temperature = float(mix.get("temperature", 0.0))
        share = mix.get("shared_prefix")
        self.prefixes = None
        if share:
            self.prefixes = [
                self.rs.randint(0, self.vocab, int(share["length"])).astype(np.int32)
                for _ in range(int(share.get("groups", 1)))
            ]
        self.n = 0

    def mean_output(self) -> float:
        return sum(o for _, o in self.cycle) / len(self.cycle)

    def next(self) -> dict:
        p, o = self.cycle[self.order[self.n % len(self.cycle)]]
        prompt = self.rs.randint(0, self.vocab, p).astype(np.int32)
        if self.prefixes is not None:
            pre = self.prefixes[self.n % len(self.prefixes)]
            k = min(pre.size, p - 1)
            prompt[:k] = pre[:k]
        self.n += 1
        return {"prompt": prompt, "max_new_tokens": int(o),
                "temperature": self.temperature}


def train_batch(vocab_size: int, batch: int, seq: int, seed: int, step: int):
    """Batch number ``step`` of a training mix: (tokens, labels), labels
    the tokens shifted by one; ids uniform, every row different."""
    rs = np.random.RandomState([seed31(seed), step])
    ids = rs.randint(0, vocab_size, (batch, seq + 1)).astype(np.int32)
    return ids[:, :-1].copy(), ids[:, 1:].copy()
