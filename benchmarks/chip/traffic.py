"""The one traffic generator: turns a mix's parameter file
(``traffic/<name>.json``) and a seed into the requests of a run.

Every seed gets the same set of sizes in another order.  Requests come in
batches of ``batch``; within a batch, prompt lengths, output lengths,
document picks and greedy flags are the batch's evenly spaced quantiles of
their distributions, each shuffled by the seed on its own.  So two seeds
load the engine with the same work, and differ in its order and in every
token.  Token ids are uniform over the vocabulary.

Keys of a mix file:

- ``clients``: closed-loop clients; each submits its next request on the
  step after its last one finished.
- ``prompt`` / ``output``: length distributions, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}`` (bounds inclusive).  With ``documents`` the prompt is the
  question that follows the document.
- ``documents``: ``null``, or ``{"count", "tokens", "zipf_s"}``: a corpus
  that set-up prefills into the prefix cache; each request picks one by a
  Zipf law of exponent ``zipf_s`` over the documents' ranks.
- ``sampling``: ``temperature`` and ``top_p`` of the sampled requests;
  ``greedy_share`` of each batch decodes greedily instead.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class RequestSpec:
    prompt: np.ndarray        # (S,) int32
    max_new_tokens: int
    greedy: bool
    document: int | None
    sampling_seed: int


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` evenly spaced quantiles of a length distribution, in
    increasing order, rounded and clipped to its bounds."""
    q = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        out = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        out = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def zipf_picks(count: int, s: float, n: int) -> np.ndarray:
    """The ``n`` evenly spaced quantiles of a Zipf law over ranks 0..count-1."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), count - 1)


class Traffic:
    """Requests of one mix under one seed; ``request(i)`` is the i-th
    request the clients submit, ``documents()`` the corpus set-up loads."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = int(seed) % 2 ** 64
        self.vocab = int(vocab)
        self.batch = int(spec["batch"])
        self._batches: dict[int, dict] = {}

    @property
    def clients(self) -> int:
        return int(self.spec["clients"])

    def _rng(self, *key) -> np.random.Generator:
        return np.random.default_rng([self.seed, *key])

    def documents(self) -> list[np.ndarray]:
        docs = self.spec.get("documents")
        if not docs:
            return []
        return [self._rng(1, d).integers(0, self.vocab, int(docs["tokens"]),
                                         dtype=np.int32)
                for d in range(int(docs["count"]))]

    def _plan(self, b: int) -> dict:
        plan = self._batches.get(b)
        if plan is None:
            n = self.batch
            rng = self._rng(2, b)
            n_greedy = int(round(n * float(self.spec["greedy_share"])))
            plan = {
                "prompt": rng.permutation(lengths(self.spec["prompt"], n)),
                "output": rng.permutation(lengths(self.spec["output"], n)),
                "greedy": rng.permutation(np.arange(n) < n_greedy),
            }
            docs = self.spec.get("documents")
            if docs:
                plan["document"] = rng.permutation(zipf_picks(
                    int(docs["count"]), float(docs["zipf_s"]), n))
            self._batches = {b: plan}      # one batch is live at a time
        return plan

    def request(self, i: int, documents: list[np.ndarray] | None = None
                ) -> RequestSpec:
        b, j = divmod(int(i), self.batch)
        plan = self._plan(b)
        tokens = self._rng(3, b, j).integers(0, self.vocab,
                                             int(plan["prompt"][j]),
                                             dtype=np.int32)
        doc = None
        if "document" in plan:
            doc = int(plan["document"][j])
            tokens = np.concatenate([documents[doc], tokens])
        return RequestSpec(tokens, int(plan["output"][j]),
                           bool(plan["greedy"][j]), doc,
                           int(self._rng(4, b, j).integers(0, 2 ** 31)))


def p_tail(values, q: float) -> float:
    """The ``q``-quantile of ``values`` by linear interpolation (numpy's
    default), used for every latency percentile the benchmark reports."""
    return float(np.percentile(np.asarray(values, np.float64), q * 100.0))
