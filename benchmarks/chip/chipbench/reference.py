"""Plain NumPy reference of the served keystream: AES-128-CTR XOF, the
uniform and Gaussian samplers, and the HERA / Rubato rounds.

It imports nothing of the system under test and is written from the
cipher descriptions and the stream convention the configuration files
state (``assumed``):

* XOF: AES-128 keyed by the 16-byte public nonce, counter block
  ``nonce[0:12] || be32(ctr * 2^16 + i)`` for AES block ``i`` of
  keystream block ``ctr``; output bytes read as little-endian uint32
  words.
* Word layout per keystream block: the round constants' words
  (``n_rc + 16``, the stream rejection sampler's budget), then ``l``
  high and ``l`` low words of the Gaussian draws (Rubato only).
* Uniform mod q: each word masked to ``bits(q)`` bits; accepted words
  (< q) taken in stream order; a slot left unfilled takes word % q.
* Gaussian: inverse CDF over ``[-tail, tail]``, ``tail = ceil(10 sigma)``,
  thresholds ``floor(cdf * 2^64)`` against the 64-bit draw ``hi:lo``.
* Rounds (both ciphers): ``ARK . [MRMC . NL . ARK]^(r-1) . MRMC . NL .
  MRMC . [truncate to l] . ARK . [AGN]`` from the state ``(1..n) mod q``;
  ``ARK(x) = x + key * rc``; ``MRMC(X) = M X M^T`` on the row-major
  ``(v, v)`` state with ``M`` circulant of first row ``[2, 3, 1, ..]``;
  ``NL`` is ``x^3`` (HERA) or the Feistel layer ``y_i = x_i + x_{i-1}^2``
  (Rubato).

Arithmetic is int64 on the host: every product of two values below
``q < 2^28`` is reduced before the next one.
"""

from __future__ import annotations

import hashlib
import math

import numpy as np

STREAM_PAD = 16          # extra words the uniform stream sampler draws
CTR_SPACE = 1 << 16      # AES blocks reserved per keystream block

# --------------------------------------------------------------------------
# AES-128 (FIPS-197)
# --------------------------------------------------------------------------


def _sbox() -> np.ndarray:
    def gmul(a, b):
        p = 0
        for _ in range(8):
            if b & 1:
                p ^= a
            hi = a & 0x80
            a = (a << 1) & 0xFF
            if hi:
                a ^= 0x1B
            b >>= 1
        return p

    inv = [0] * 256
    for a in range(1, 256):
        for b in range(1, 256):
            if gmul(a, b) == 1:
                inv[a] = b
                break
    box = []
    for a in range(256):
        x = inv[a]
        s = x
        for k in range(1, 5):
            s ^= ((x << k) | (x >> (8 - k))) & 0xFF
        box.append(s ^ 0x63)
    return np.array(box, np.uint8)


SBOX = _sbox()
XTIME = np.array([((a << 1) ^ (0x1B if a & 0x80 else 0)) & 0xFF
                  for a in range(256)], np.uint8)
# state byte index = 4 * column + row; ShiftRows moves row r left by r
SHIFT_ROWS = np.array([4 * ((c + r) % 4) + r
                       for c in range(4) for r in range(4)])
RCON = [0x01, 0x02, 0x04, 0x08, 0x10, 0x20, 0x40, 0x80, 0x1B, 0x36]


def aes128_round_keys(key: bytes) -> np.ndarray:
    """(11, 16) uint8 round keys of a 16-byte key."""
    w = [list(key[4 * i:4 * i + 4]) for i in range(4)]
    for i in range(4, 44):
        t = list(w[i - 1])
        if i % 4 == 0:
            t = t[1:] + t[:1]
            t = [int(SBOX[b]) for b in t]
            t[0] ^= RCON[i // 4 - 1]
        w.append([a ^ b for a, b in zip(w[i - 4], t)])
    return np.array(w, np.uint8).reshape(11, 16)


def aes128_encrypt(blocks: np.ndarray, round_keys: np.ndarray) -> np.ndarray:
    """Encrypt (N, 16) uint8 blocks under one key's round keys."""
    s = blocks ^ round_keys[0]
    for rnd in range(1, 11):
        s = SBOX[s][:, SHIFT_ROWS]
        if rnd < 10:
            c = s.reshape(-1, 4, 4)
            a0, a1, a2, a3 = c[:, :, 0], c[:, :, 1], c[:, :, 2], c[:, :, 3]
            t = a0 ^ a1 ^ a2 ^ a3
            c = np.stack([a0 ^ t ^ XTIME[a0 ^ a1], a1 ^ t ^ XTIME[a1 ^ a2],
                          a2 ^ t ^ XTIME[a2 ^ a3], a3 ^ t ^ XTIME[a3 ^ a0]],
                         axis=-1)
            s = c.reshape(-1, 16)
        s = s ^ round_keys[rnd]
    return s


def xof_words(nonce: bytes, ctrs: np.ndarray, n_words: int) -> np.ndarray:
    """(lanes, n_words) uint32 XOF words for keystream blocks ``ctrs``."""
    ctrs = np.asarray(ctrs, np.uint64).reshape(-1)
    n_blocks = -(-n_words // 4)
    idx = (ctrs[:, None] * CTR_SPACE
           + np.arange(n_blocks, dtype=np.uint64)[None, :]).reshape(-1)
    idx = (idx & 0xFFFFFFFF).astype(np.uint32)
    blocks = np.empty((idx.size, 16), np.uint8)
    blocks[:, :12] = np.frombuffer(nonce[:12], np.uint8)
    blocks[:, 12:] = idx.astype(">u4").view(np.uint8).reshape(-1, 4)
    out = aes128_encrypt(blocks, aes128_round_keys(nonce))
    words = np.ascontiguousarray(out).view("<u4")
    words = words.reshape(ctrs.size, n_blocks * 4)
    return words[:, :n_words].astype(np.uint32)


# --------------------------------------------------------------------------
# Cipher description and samplers
# --------------------------------------------------------------------------


class Cipher:
    """The numbers of one parameter set, as its configuration file gives
    them (``cipher``: kind, n, l, rounds, q, sigma)."""

    def __init__(self, spec: dict):
        self.kind = spec["kind"]
        if self.kind not in ("hera", "rubato"):
            raise ValueError(f"reference covers hera and rubato, not "
                             f"{self.kind!r}")
        self.n, self.l, self.r = spec["n"], spec["l"], spec["rounds"]
        self.q = spec["q"]
        self.sigma = float(spec.get("sigma", 0.0))
        self.v = math.isqrt(self.n)
        if self.v * self.v != self.n:
            raise ValueError("n must be a perfect square")
        first = [2, 3] + [1] * (self.v - 2)
        self.M = np.array([np.roll(first, i) for i in range(self.v)],
                          np.int64)

    @property
    def n_rc(self) -> int:
        """Round constants per block: (r+1)n for HERA; Rubato's last ARK
        feeds a truncation, so r*n + l."""
        if self.kind == "hera":
            return (self.r + 1) * self.n
        return self.r * self.n + self.l

    @property
    def n_noise(self) -> int:
        return self.l if self.kind == "rubato" and self.sigma > 0 else 0

    @property
    def words_per_block(self) -> int:
        return self.n_rc + STREAM_PAD + 2 * self.n_noise

    def gauss_thresholds(self) -> tuple:
        tail = int(math.ceil(10 * self.sigma))
        xs = np.arange(-tail, tail + 1).astype(np.float64)
        w = np.exp(-(xs ** 2) / (2 * self.sigma ** 2))
        cdf = np.cumsum(w / w.sum())[:-1]
        fixed = np.minimum(np.floor(cdf * float(2 ** 64)), float(2 ** 64 - 1))
        return tail, np.array([int(f) for f in fixed], np.uint64)

    def constants(self, words: np.ndarray) -> tuple:
        """XOF words -> (rc (lanes, n_rc) int64, noise (lanes, l) or None)."""
        q = self.q
        w_u = self.n_rc + STREAM_PAD
        cand = words[:, :w_u].astype(np.int64) & ((1 << q.bit_length()) - 1)
        ok = cand < q
        order = np.argsort(~ok, axis=1, kind="stable")[:, :self.n_rc]
        picked = np.take_along_axis(cand, order, axis=1)
        rc = np.where(np.take_along_axis(ok, order, axis=1), picked,
                      picked % q)
        noise = None
        if self.n_noise:
            hi = words[:, w_u:w_u + self.l].astype(np.uint64)
            lo = words[:, w_u + self.l:w_u + 2 * self.l].astype(np.uint64)
            tail, thr = self.gauss_thresholds()
            u = (hi << np.uint64(32)) | lo
            noise = np.searchsorted(thr, u, side="right").astype(np.int64) \
                - tail
        return rc, noise

    # ---- rounds ----------------------------------------------------------
    def _mrmc(self, x):
        X = x.reshape(-1, self.v, self.v)
        Y = np.einsum("ij,bjk->bik", self.M, X) % self.q
        Z = np.einsum("bij,kj->bik", Y, self.M) % self.q
        return Z.reshape(-1, self.n)

    def _nl(self, x):
        q = self.q
        if self.kind == "hera":
            return x * x % q * x % q
        y = x.copy()
        y[:, 1:] = (x[:, 1:] + x[:, :-1] * x[:, :-1] % q) % q
        return y

    def keystream(self, key: np.ndarray, rc: np.ndarray, noise) -> np.ndarray:
        """(lanes, l) keystream from constants under ``key`` (n,)."""
        q, n, l = self.q, self.n, self.l
        key = np.asarray(key, np.int64)
        x = np.broadcast_to(np.arange(1, n + 1, dtype=np.int64) % q,
                            (rc.shape[0], n))
        x = (x + key * rc[:, :n] % q) % q
        for j in range(1, self.r):
            x = self._nl(self._mrmc(x))
            x = (x + key * rc[:, j * n:(j + 1) * n] % q) % q
        x = self._mrmc(self._nl(self._mrmc(x)))[:, :l]
        a = self.r * n
        x = (x + key[:l] * rc[:, a:a + l] % q) % q
        if noise is not None:
            x = (x + noise % q) % q
        return x

    def keystream_for(self, key, nonce: bytes, ctrs) -> np.ndarray:
        """The reference keystream of blocks ``ctrs`` under one nonce."""
        words = xof_words(nonce, ctrs, self.words_per_block)
        rc, noise = self.constants(words)
        return self.keystream(key, rc, noise)


def tenant_key(preset: str, n: int, q: int, tenant_id: str,
               seed: int) -> np.ndarray:
    """The key the configuration provisions for a tenant:
    SHA-256("preset|tenant|seed") seeds NumPy's default generator, which
    draws n words uniform in [1, q)."""
    digest = hashlib.sha256(f"{preset}|{tenant_id}|{seed}".encode()).digest()
    rng = np.random.default_rng(np.frombuffer(digest, np.uint64))
    return rng.integers(1, q, size=(n,), dtype=np.uint32)
