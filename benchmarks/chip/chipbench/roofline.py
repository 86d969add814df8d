"""Bytes the keystream kernel needs per block, from the cipher's numbers
alone: the round constants, the noise and the matrix words it reads and
the l words it writes, 4 bytes each."""

from __future__ import annotations


def words_per_block(cipher) -> dict:
    """Words in and out of the kernel per keystream block; ``cipher`` is a
    `chipbench.reference.Cipher` (HERA and Rubato stream no matrices)."""
    return {"round_constants": cipher.n_rc, "noise": cipher.n_noise,
            "matrix": 0, "out": cipher.l}


def bytes_per_block(cipher) -> int:
    return 4 * sum(words_per_block(cipher).values())
