"""The benchmark's NumPy reference against FIPS-197 and against the
program's own oracle, and the kernel's bytes per block against the
program's CipherParams, for both configurations."""

import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import reference  # noqa: E402
from chipbench.roofline import bytes_per_block, words_per_block  # noqa: E402

CONFIGS = ("rubato-128l", "hera-128a")


def spec(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_aes_matches_fips_197():
    key = bytes(range(16))
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"),
                       np.uint8)[None]
    ct = reference.aes128_encrypt(pt, reference.aes128_round_keys(key))
    assert ct.tobytes().hex() == "69c4e0d86a7b0430d8cdb78070b4c55a"


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_the_programs_oracle(name):
    from repro.core.cipher import Cipher
    from repro.core.params import get_params
    from repro.serve.tenants import derive_tenant_key

    p = get_params(name)
    cfg = spec(name)
    c = reference.Cipher(cfg["cipher"])
    assert (c.n, c.l, c.r, c.q) == (p.n, p.l, p.rounds, p.mod.q)
    assert cfg["cipher"]["q_bits"] == p.mod.bits
    assert c.words_per_block == p.xof_words_per_block() == \
        cfg["cipher"]["xof_words_per_block"]
    seed = 2**31 + 5
    key = reference.tenant_key(name, p.n, p.mod.q, "t3", seed)
    np.testing.assert_array_equal(key, derive_tenant_key(name, "t3", seed))
    nonce = np.random.default_rng(9).integers(0, 256, 16, dtype=np.uint8)
    ctrs = np.array([0, 1, 2, 63, 64, 4095, 65535], np.uint32)
    want = np.asarray(Cipher(p, key, nonce).keystream(ctrs))
    got = c.keystream_for(key, nonce.tobytes(), ctrs)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", CONFIGS)
def test_bytes_per_block_follow_the_parameter_set(name):
    from repro.core.params import get_params

    p = get_params(name)
    c = reference.Cipher(spec(name)["cipher"])
    w = words_per_block(c)
    assert w == {"round_constants": p.n_round_constants,
                 "noise": p.n_noise, "matrix": p.n_matrix_constants,
                 "out": p.l}
    assert bytes_per_block(c) == 4 * (p.n_round_constants + p.n_noise
                                      + p.n_matrix_constants + p.l)
    assert bytes_per_block(c) == {"rubato-128l": 1232,
                                  "hera-128a": 448}[name]


def test_reference_refuses_a_cipher_it_does_not_cover():
    with pytest.raises(ValueError, match="hera and rubato"):
        reference.Cipher({"kind": "pasta", "n": 32, "l": 16, "rounds": 4,
                          "q": 67104769})
