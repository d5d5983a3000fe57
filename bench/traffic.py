"""The one traffic generator: every mix is a data file it reads.

A traffic file (``bench/traffic/<name>.json``) gives:

* ``loop``: ``"closed"`` -- ``workers`` callers, each submitting its next
  request in the engine iteration after its previous answer ended; or
  ``"open"`` -- independent users, Poisson arrivals at ``rate_per_s``
  in wall time, starting ``preroll_s`` before the measured window;
* ``prompt`` and ``output``: length distributions (see :func:`draw`);
* ``documents`` (optional): ``count`` shared documents of ``tokens``
  tokens each, asked round-robin and prefilled during set-up -- a
  request's prompt is its document followed by its own question; a
  ``bare_share`` of the requests send the document alone (no question
  of their own), so that their whole prompt is a prefix-cache hit;
* ``schedule_seed`` and ``table_size``: the lengths, documents and
  arrival offsets are drawn once from ``schedule_seed``, so every run
  seed serves the same schedule;
* ``engine``: the ``PagedEngine`` flags of the cell.

The run's ``--seed`` draws only the token ids (and the weights).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_DOC, _REQ = 0, 1


def draw(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from a distribution spec:

    * ``uniform``: ``min``..``max`` inclusive;
    * ``lognormal``: median ``median``, log-sd ``sigma``, clipped to
      ``min``..``max``;
    * ``pareto``: ``min * U**(-1/alpha)`` for U uniform in (0, 1], a
      clipped power law (discrete Zipf-like) whose median is
      ``min * 2**(1/alpha)``, clipped to ``max``.
    """
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    elif kind == "lognormal":
        x = np.round(rng.lognormal(np.log(dist["median"]), dist["sigma"],
                                   size=n))
    elif kind == "pareto":
        u = 1.0 - rng.random(size=n)                 # (0, 1]
        x = np.floor(lo * u ** (-1.0 / dist["alpha"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(x, lo, hi).astype(np.int64)


@dataclass
class Schedule:
    """Lengths and arrivals of a mix, independent of the run's seed.

    Request ``i`` has question/prompt length ``prompt_len[i % T]`` (0 for
    a request that sends its document alone),
    output length ``output_len[i % T]`` and, with documents, document
    ``i % n_docs``.  An open loop's request ``i`` is due ``arrival_s[i]``
    seconds after the window opens (negative: during the pre-roll)."""

    spec: dict
    prompt_len: np.ndarray
    output_len: np.ndarray
    arrival_s: np.ndarray | None
    n_docs: int
    doc_tokens: int

    def request(self, i: int) -> tuple[int, int, int]:
        """(document or -1, own prompt tokens, output tokens) of request i."""
        t = len(self.prompt_len)
        doc = i % self.n_docs if self.n_docs else -1
        return doc, int(self.prompt_len[i % t]), int(self.output_len[i % t])

    @property
    def max_len(self) -> int:
        """Longest prompt plus output of any request."""
        return (self.doc_tokens + int(self.prompt_len.max())
                + int(self.output_len.max()))


def schedule(spec: dict) -> Schedule:
    rng = np.random.default_rng(spec["schedule_seed"])
    n = int(spec["table_size"])
    prompt_len = draw(spec["prompt"], n, rng)
    output_len = draw(spec["output"], n, rng)
    arrival_s = None
    if spec["loop"] == "open":
        gaps = rng.exponential(1.0 / spec["rate_per_s"], size=n)
        arrival_s = np.cumsum(gaps) - spec["preroll_s"]
    elif spec["loop"] != "closed":
        raise ValueError(f"unknown loop {spec['loop']!r}")
    docs = spec.get("documents") or {}
    bare = float(docs.get("bare_share", 0.0))
    if bare:
        if not docs.get("tokens"):
            raise ValueError("bare_share needs documents: a request "
                             "would have an empty prompt")
        prompt_len[rng.random(size=n) < bare] = 0
    return Schedule(spec, prompt_len, output_len, arrival_s,
                    int(docs.get("count", 0)), int(docs.get("tokens", 0)))


def _rng(seed: int, stream: int, index: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 63, stream, index])


def token_ids(seed: int, stream: int, index: int, n: int,
              vocab: int) -> np.ndarray:
    """``n`` token ids in ``[1, vocab)`` for (seed, stream, index)."""
    return _rng(seed, stream, index).integers(1, vocab, size=n,
                                              dtype=np.int64)


def document(seed: int, d: int, sched: Schedule, vocab: int) -> np.ndarray:
    return token_ids(seed, _DOC, d, sched.doc_tokens, vocab)


def prompt(seed: int, i: int, sched: Schedule, vocab: int) -> np.ndarray:
    """Request i's whole prompt: its document (if any) then its own
    tokens."""
    doc, n, _ = sched.request(i)
    own = token_ids(seed, _REQ, i, n, vocab)
    if doc < 0:
        return own
    return np.concatenate([document(seed, doc, sched, vocab), own])
