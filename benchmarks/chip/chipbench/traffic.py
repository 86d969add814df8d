"""The one traffic generator: a mix file's parameters and a seed in, the
requests of a run out.

A mix is a JSON file under ``traffic/``:

* ``loop``: ``"closed"`` (each connection keeps one request in flight
  and sends the next when it returns) or ``"open"`` (requests
  are due on a schedule, whatever the replies do);
* ``connections_per_tenant``: connections a tenant opens, each with a
  session of its own;
* ``tenant_skew``: ``{"kind": "uniform"}`` or ``{"kind": "zipf",
  "theta": t}`` (share of tenant i proportional to ``1 / (i+1)^t``);
* ``ops``: share of each wire ``hhe_op``;
* ``size``: ``{"words": w}`` (a tensor of w words, cut into blocks of l
  and the last block filled up with further random words) or
  ``{"blocks_min": a, "blocks_max": b}`` (uniform in [a, b]);
* open loop only: ``arrivals: "poisson"`` at the cell's ``rate_per_s``.

An open-loop schedule is stratified so that every seed sends the same
work: the tenant, op and size counts are the exact shares (largest
remainder), the gaps are the exponential distribution's quantiles, and
the seed only orders them.  Payloads are uniform in [0, q).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    """One request as the generator makes it; the harness times it."""

    index: int
    tenant: int
    conn: int
    op: str
    payload: np.ndarray          # (blocks, l) uint32 in [0, q)
    words: int                   # payload words the user asked to move
    due: float = 0.0             # seconds after the window opens (open loop)

    @property
    def blocks(self) -> int:
        return self.payload.shape[0]


def exact_counts(shares, total: int) -> np.ndarray:
    """Split ``total`` by ``shares`` with the largest-remainder rule."""
    shares = np.asarray(shares, np.float64)
    raw = shares / shares.sum() * total
    counts = np.floor(raw).astype(np.int64)
    left = total - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:left]] += 1
    return counts


def tenant_shares(mix: dict, tenants: int) -> np.ndarray:
    skew = mix.get("tenant_skew", {"kind": "uniform"})
    if skew["kind"] == "uniform":
        return np.full(tenants, 1.0 / tenants)
    if skew["kind"] == "zipf":
        w = 1.0 / np.arange(1, tenants + 1, dtype=np.float64) ** skew["theta"]
        return w / w.sum()
    raise ValueError(f"unknown tenant skew {skew['kind']!r}")


def blocks_for(size: dict, l: int) -> tuple:
    """(blocks, words) of a fixed-size request."""
    words = int(size["words"])
    return -(-words // l), words


class Generator:
    """Requests of one mix for one run, all drawn from ``seed``."""

    def __init__(self, mix: dict, *, tenants: int, l: int, q: int,
                 seed: int):
        if mix["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.mix, self.tenants, self.l, self.q = mix, tenants, l, q
        self.seed = seed
        self.conns = int(mix.get("connections_per_tenant", 1))
        self.ops = sorted(mix["ops"])
        self.op_shares = [mix["ops"][o] for o in self.ops]

    def _payload(self, rng, blocks: int) -> np.ndarray:
        return rng.integers(0, self.q, (blocks, self.l), dtype=np.uint32)

    # ---- closed loop -----------------------------------------------------
    def closed_stream(self, tenant: int, conn: int):
        """Endless requests of one closed-loop connection, in order."""
        rng = np.random.default_rng([self.seed, 1, tenant, conn])
        size = self.mix["size"]
        k = 0
        while True:
            op = self.ops[int(rng.choice(len(self.ops), p=self._p()))]
            if "words" in size:
                blocks, words = blocks_for(size, self.l)
            else:
                blocks = int(rng.integers(size["blocks_min"],
                                          size["blocks_max"] + 1))
                words = blocks * self.l
            yield Request(k, tenant, conn, op, self._payload(rng, blocks),
                          words)
            k += 1

    def _p(self):
        s = np.asarray(self.op_shares, np.float64)
        return s / s.sum()

    # ---- open loop -------------------------------------------------------
    def open_schedule(self, rate_per_s: float, seconds: float) -> list:
        """The window's requests, due in (0, seconds], in due order."""
        if self.mix.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {self.mix['arrivals']!r}")
        n = max(1, int(round(rate_per_s * seconds)))
        rng = np.random.default_rng([self.seed, 2])
        tenants = rng.permutation(np.repeat(
            np.arange(self.tenants),
            exact_counts(tenant_shares(self.mix, self.tenants), n)))
        ops = rng.permutation(np.repeat(
            np.arange(len(self.ops)), exact_counts(self.op_shares, n)))
        size = self.mix["size"]
        if "words" in size:
            blocks, words = blocks_for(size, self.l)
            sizes = np.full(n, blocks)
        else:
            choices = np.arange(size["blocks_min"], size["blocks_max"] + 1)
            sizes = rng.permutation(np.repeat(
                choices, exact_counts(np.ones(choices.size), n)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
        gaps = rng.permutation(gaps)
        due = np.cumsum(gaps) * (seconds / gaps.sum())
        seen = np.zeros(self.tenants, np.int64)
        out = []
        for i in range(n):
            t = int(tenants[i])
            conn = int(seen[t] % self.conns)
            seen[t] += 1
            b = int(sizes[i])
            w = words if "words" in size else b * self.l
            out.append(Request(i, t, conn, self.ops[int(ops[i])],
                               self._payload(rng, b), w, float(due[i])))
        return out

    def warmup_requests(self) -> list:
        """One request of every (op, size) the mix sends, for each tenant
        on its first connection: every shape the window will use."""
        rng = np.random.default_rng([self.seed, 3])
        size = self.mix["size"]
        if "words" in size:
            shapes = [blocks_for(size, self.l)]
        else:
            shapes = [(b, b * self.l) for b in
                      range(size["blocks_min"], size["blocks_max"] + 1)]
        out = []
        for t in range(self.tenants):
            for op in self.ops:
                for blocks, words in shapes:
                    out.append(Request(len(out), t, 0, op,
                                       self._payload(rng, blocks), words))
        return out


def nearest_rank(values, p: float) -> float:
    """The p-quantile (0 < p <= 1) by the nearest-rank rule."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return float(v[max(0, math.ceil(p * len(v)) - 1)])
