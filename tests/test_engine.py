"""KeystreamEngine registry: capability reporting, single-place "auto"
resolution, and the cross-backend bit-exactness matrix (ISSUE acceptance:
every registered engine produces identical keystream for HERA, Rubato,
AND PASTA across all CipherParams presets, with and without AGN noise,
under both schedule-orientation variants).

scripts/ci.sh runs this file in its engine-matrix stage so backend drift
fails fast.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    CipherBatch,
    KeystreamFarm,
    engine_caps,
    make_cipher,
    make_engine,
    registered_engines,
    resolve_engine,
)
from repro.core.engine import PallasInterpretEngine
from repro.core.params import get_params
from repro.kernels.keystream.ref import keystream_ref

# every preset in core/params.py REGISTRY; every engine that can run on any
# backend (compiled "pallas" and "sharded" need TPU / a mesh — covered
# separately below); both schedule-orientation variants (core/schedule.py)
PRESETS = ["hera-128a", "rubato-128s", "rubato-128m", "rubato-128l",
           "pasta-128s", "pasta-128l"]
PORTABLE_ENGINES = ["ref", "jax", "pallas-interpret"]
VARIANTS = ["normal", "alternating"]
LANES = 3


def _constants(name, with_noise):
    ci = make_cipher(name, seed=17)
    consts = ci.round_constant_stream(jnp.arange(LANES, dtype=jnp.uint32))
    noise = consts["noise"] if with_noise else None
    return ci, consts["rc"], noise, consts.get("mats")


# ---------------------------------------------------------------------------
# The engine matrix: bit-exactness across backends and schedule variants
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("with_noise", [False, True])
@pytest.mark.parametrize("name", PRESETS)
@pytest.mark.parametrize("engine", PORTABLE_ENGINES)
def test_engine_matrix_bit_exact(engine, name, with_noise, variant):
    p = get_params(name)
    if with_noise and not p.n_noise:
        pytest.skip("preset has no AGN noise (HERA)")
    ci, rc, noise, mats = _constants(name, with_noise)
    want = np.array(keystream_ref(p, ci.key, rc, noise, mats=mats))
    eng = make_engine(engine, p, ci.key, variant=variant)
    assert eng.variant == variant
    got = np.array(eng.keystream_from_constants(rc, noise, mats))
    np.testing.assert_array_equal(got, want)
    assert got.shape == (LANES, p.l)


def test_sharded_engine_matches_ref_on_host_mesh():
    """'sharded' needs a mesh; on a 1-wide axis it must equal the oracle.
    It compiles its kernel unless interpret mode is asked for explicitly,
    as a CPU host must."""
    ci = make_cipher("hera-128a", seed=17)
    mesh = jax.make_mesh((1,), ("data",))
    assert not make_engine("sharded", ci.params, ci.key, mesh=mesh).interpret
    eng = make_engine("sharded", ci.params, ci.key, mesh=mesh, interpret=True)
    rc = ci.round_constant_stream(jnp.arange(LANES, dtype=jnp.uint32))["rc"]
    np.testing.assert_array_equal(
        np.array(eng.keystream_from_constants(rc)),
        np.array(keystream_ref(ci.params, ci.key, rc, None)))


def test_engines_consume_constants_dict():
    ci, rc, noise, _ = _constants("rubato-128s", True)
    eng = make_engine("jax", ci.params, ci.key)
    np.testing.assert_array_equal(
        np.array(eng({"rc": rc, "noise": noise})),
        np.array(keystream_ref(ci.params, ci.key, rc, noise)))


# ---------------------------------------------------------------------------
# Registry + capability reporting
# ---------------------------------------------------------------------------
def test_registry_contents():
    assert set(registered_engines()) >= {
        "ref", "jax", "pallas", "pallas-interpret", "sharded"}


def test_engine_caps_report():
    caps = engine_caps()
    assert set(caps) == set(registered_engines())
    assert caps["ref"].available and caps["jax"].available
    assert caps["pallas-interpret"].available
    assert caps["pallas-interpret"].max_lanes is not None
    # sharded without a mesh is unavailable, with a reason
    assert not caps["sharded"].available and caps["sharded"].reason
    assert engine_caps(mesh=jax.make_mesh((1,), ("data",)))[
        "sharded"].available
    if jax.default_backend() != "tpu":
        assert not caps["pallas"].available
        assert "pallas-interpret" in caps["pallas"].reason
    # schedule-variant reporting: every backend executes both orientation
    # plans; the unrolled kernel prefers the bubble-free alternating one
    for c in caps.values():
        assert set(c.schedule_variants) == {"normal", "alternating"}
        assert c.preferred_variant in c.schedule_variants
    assert caps["pallas"].preferred_variant == "alternating"
    assert caps["ref"].preferred_variant == "normal"


def test_engine_variant_auto_and_validation():
    ci = make_cipher("hera-128a", seed=1)
    eng = make_engine("pallas-interpret", ci.params, ci.key, variant="auto")
    assert eng.variant == "alternating"
    assert eng.schedule.name == "hera-128a/alternating"
    assert make_engine("jax", ci.params, ci.key, variant="auto").variant == \
        "normal"
    with pytest.raises(ValueError, match="schedule variant"):
        make_engine("ref", ci.params, ci.key, variant="diagonal")


def test_make_engine_instance_variant_contract():
    """A pre-bound engine passes through with its own plan (variant
    unspecified or matching), but an explicit contradicting variant must
    raise rather than be silently ignored."""
    ci = make_cipher("hera-128a", seed=1)
    eng = make_engine("jax", ci.params, ci.key, variant="alternating")
    assert make_engine(eng, ci.params, ci.key) is eng
    assert make_engine(eng, ci.params, ci.key,
                       variant="alternating") is eng
    with pytest.raises(ValueError, match="already executes"):
        make_engine(eng, ci.params, ci.key, variant="normal")
    with pytest.raises(ValueError, match="already executes"):
        KeystreamFarm(_batch_for(ci), engine=eng, variant="normal")


def _batch_for(ci):
    cb = CipherBatch(ci.params, key=np.asarray(ci.key), seed=9)
    cb.add_session()
    return cb


def test_engine_describe_table():
    from repro.core.engine import describe
    text = describe()
    for name in registered_engines():
        assert name in text
    assert "auto resolves to" in text


def test_resolve_auto_matches_backend():
    want = "pallas" if jax.default_backend() == "tpu" else "jax"
    assert resolve_engine("auto") == want


def test_resolve_legacy_kernel_alias():
    assert resolve_engine("kernel", interpret=True) == "pallas-interpret"
    assert resolve_engine("kernel", interpret=False) == "pallas"
    assert resolve_engine("pallas", interpret=True) == "pallas-interpret"
    if jax.default_backend() != "tpu":
        assert resolve_engine("kernel") == "pallas-interpret"
    # legacy "kernel" with a mesh sharded the lane axis; so does the alias
    mesh = jax.make_mesh((1,), ("data",))
    assert resolve_engine("kernel", mesh=mesh) == "sharded"


def test_farm_legacy_kernel_with_mesh_shards_and_matches():
    cb = CipherBatch("hera-128a", seed=6)
    cb.add_session()
    mesh = jax.make_mesh((1,), ("data",))
    farm = KeystreamFarm(cb, consumer="kernel", mesh=mesh, interpret=True)
    assert farm.engine.name == "sharded"
    z = np.array(farm.keystream(np.zeros(4, np.int64), np.arange(4)))
    want = np.array(cb.session_cipher(0).keystream(
        jnp.arange(4, dtype=jnp.uint32)))
    np.testing.assert_array_equal(z, want)


def test_unknown_engine_raises_listing_registry():
    with pytest.raises(ValueError, match="registered engines"):
        resolve_engine("vulkan")


def test_unavailable_engine_raises_with_reason():
    ci = make_cipher("hera-128a", seed=1)
    with pytest.raises(RuntimeError, match="needs a mesh"):
        make_engine("sharded", ci.params, ci.key)
    if jax.default_backend() != "tpu":
        with pytest.raises(RuntimeError, match="unavailable"):
            make_engine("pallas", ci.params, ci.key)


def test_interpret_engine_lane_cap():
    ci = make_cipher("hera-128a", seed=1)
    eng = make_engine("pallas-interpret", ci.params, ci.key)
    too_many = jnp.zeros(
        (PallasInterpretEngine.MAX_LANES + 1, ci.params.n_round_constants),
        jnp.uint32)
    with pytest.raises(ValueError, match="caps lanes"):
        eng.keystream_from_constants(too_many)


def test_make_engine_passes_instances_through():
    ci = make_cipher("hera-128a", seed=1)
    eng = make_engine("ref", ci.params, ci.key)
    assert make_engine(eng, ci.params, ci.key) is eng


def test_make_engine_rejects_mismatched_instance():
    """A pre-bound engine keyed differently from the pool would silently
    emit unmatchable keystream — must fail loudly instead."""
    a = make_cipher("hera-128a", seed=1)
    b = make_cipher("hera-128a", seed=2)
    r = make_cipher("rubato-128s", seed=1)
    eng = make_engine("ref", a.params, a.key)
    with pytest.raises(ValueError, match="different \\(params, key\\)"):
        make_engine(eng, b.params, b.key)      # same params, other key
    with pytest.raises(ValueError, match="different \\(params, key\\)"):
        make_engine(eng, r.params, r.key)      # other cipher entirely
    cb = CipherBatch("hera-128a", seed=9)
    cb.add_session()
    with pytest.raises(ValueError, match="different \\(params, key\\)"):
        KeystreamFarm(cb, engine=eng)


# ---------------------------------------------------------------------------
# Engine-routed call sites
# ---------------------------------------------------------------------------
def test_farm_accepts_engine_instance():
    """The farm consumer is pluggable: a pre-bound engine instance works."""
    cb = CipherBatch("rubato-128s", seed=3)
    cb.add_session()
    eng = cb.make_engine("jax")
    farm = KeystreamFarm(cb, engine=eng)
    assert farm.engine is eng and farm.consumer == "jax"
    sids, ctrs = np.zeros(4, np.int64), np.arange(4)
    z = np.array(farm.keystream(sids, ctrs))
    want = np.array(cb.session_cipher(0).keystream(
        jnp.arange(4, dtype=jnp.uint32)))
    np.testing.assert_array_equal(z, want)


def test_farm_rejects_engine_and_consumer_together():
    cb = CipherBatch("hera-128a", seed=3)
    cb.add_session()
    with pytest.raises(ValueError, match="not both"):
        KeystreamFarm(cb, engine="jax", consumer="jax")


def test_cipher_engine_override_bit_exact():
    ref = make_cipher("rubato-128l", seed=5)
    jit = make_cipher("rubato-128l", seed=5, engine="jax")
    ctrs = jnp.arange(4, dtype=jnp.uint32)
    np.testing.assert_array_equal(np.array(ref.keystream(ctrs)),
                                  np.array(jit.keystream(ctrs)))
