"""The one generator of inputs: serving requests and training batches,
from a traffic file's parameters and ``--seed``.

Sizes (prompt and output lengths, task counts) are drawn at fixed
quantiles of the traffic file's distributions, so every seed gets the same
multiset; the seed draws the token ids, and what else it orders is said
where it does (serving requests, training batches).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 63, *salt])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of a lognormal of the
    given median and sigma, clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_counts(n_items: int, s: float, n: int) -> np.ndarray:
    """Item ids of ``n`` draws from Zipf(s) over ``n_items``, as a fixed
    multiset: item k gets round(n * p_k) draws (largest remainders)."""
    p = 1.0 / np.arange(1, n_items + 1) ** s
    p = p / p.sum()
    raw = p * n
    cnt = np.floor(raw).astype(np.int64)
    for k in np.argsort(-(raw - cnt))[: n - int(cnt.sum())]:
        cnt[k] += 1
    return np.repeat(np.arange(n_items), cnt)


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    prompt: np.ndarray
    max_new: int
    task: int


def serve_requests(traffic: dict, vocab: int, seed: int, n: int,
                   stream: int = 0) -> list:
    """``n`` requests of the traffic mix; ``stream`` separates the window's
    requests (0) from warm-up (1) and traced (2) ones.

    The list is made of blocks of ``traffic["block"]`` requests, each block
    the same multiset of prompt and output lengths in an order of its own.
    That order is the same for every seed: the engine runs every request to
    its length, so the lengths and their order fix the schedule, and every
    seed then asks for the same work (a window serves only a prefix of the
    list). The seed draws the token ids and each block's order of tasks.
    With ``traffic["first_block"]`` the first block is cut to what would
    be left of a running batch."""
    blk = min(traffic.get("block", n), n)
    nblk = -(-n // blk)
    fixed = _rng(0, 1, stream)
    plen, olen = (np.concatenate([fixed.permutation(x)
                                  for _ in range(nblk)])[:n]
                  for x in (quantile_lengths(traffic["prompt"], blk),
                            quantile_lengths(traffic["output"], blk)))
    first = traffic.get("first_block")
    if first is not None:
        # The first block stands for a batch already under way when the
        # call opens, so that the call is in steady continuous batching
        # from its first steps: each request has ``prompt_left`` prompt
        # tokens still to prefill and a share (i + 1/2) / blk of its
        # output left, in a fixed order. Its outputs are drawn length-
        # biased, as a running batch holds long requests more often (for
        # a lognormal: the same sigma, the median times exp(sigma^2)).
        spec = dict(traffic["output"])
        spec["median"] *= math.exp(spec["sigma"] ** 2)
        whole = fixed.permutation(quantile_lengths(spec, blk))
        left = (fixed.permutation(blk) + 0.5) / blk
        olen[:blk] = np.maximum(1, np.ceil(whole * left))
        plen[:blk] = np.minimum(plen[:blk], first["prompt_left"])
    rng = _rng(seed, 1, stream)
    tasks = np.concatenate([rng.permutation(zipf_counts(
        traffic["tasks"]["count"], traffic["tasks"]["zipf"], blk))
        for _ in range(nblk)])[:n]
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(plen[i]), dtype=np.int32)
        out.append(ServeRequest(toks, int(olen[i]), int(tasks[i])))
    return out


class MarkovFeed:
    """Training batches: token rows of an order-1 Markov chain over the
    vocabulary with out-degree ``branching`` (the arithmetic of the
    program's ``repro.data.LMStream``), so next-token loss has signal.
    Batch ``i`` depends only on (seed, i): every row of every step differs.
    ``__next__`` yields {"tokens", "mask"} as the Trainer takes them."""

    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int,
                 branching: int = 4, on_batch=None):
        rng = _rng(seed, 2)
        self.vocab, self.seq_len, self.batch = vocab, seq_len, batch
        self.seed, self.branching = seed, branching
        self._next = rng.integers(0, vocab, (vocab, branching))
        self._cum = np.cumsum(rng.dirichlet(np.ones(branching), vocab), -1)
        self.step = 0
        self.on_batch = on_batch

    def batch_at(self, i: int) -> dict:
        rng = _rng(self.seed, 3, i)
        toks = np.empty((self.batch, self.seq_len), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, self.batch)
        u = rng.random((self.batch, self.seq_len))
        for t in range(1, self.seq_len):
            prev = toks[:, t - 1]
            c = np.minimum((u[:, t, None] > self._cum[prev]).sum(-1),
                           self.branching - 1)
            toks[:, t] = self._next[prev, c]
        return {"tokens": toks, "mask": np.ones_like(toks, np.float32)}

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        if self.on_batch is not None:
            with self.on_batch():
                b = self.batch_at(self.step)
        else:
            b = self.batch_at(self.step)
        self.step += 1
        return b
