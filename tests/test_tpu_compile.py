"""Compile-only checks for a described (not attached) TPU v5e.

Interpret mode never reaches Mosaic, the TPU kernel compiler, so the
interpret-mode kernel tests cannot see what the chip's compiler refuses:
unsigned reductions, scoped VMEM over the limit, unaligned slices.  These
tests lower the fused keystream kernel with ``interpret=False`` for every
preset, and the ``sharded`` engine over a 2x2 v5e mesh, through the TPU
compiler installed on the host.  Nothing runs; no chip is needed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine import make_engine
from repro.core.params import REGISTRY, get_params
from repro.kernels.keystream.keystream import BLK
from repro.kernels.keystream.ops import keystream_kernel_apply

#: every preset in the normal variant, plus the alternating schedule of
#: one HERA/Rubato preset (its transposed ARKs permute the rc plane)
KERNEL_CASES = ([(name, "normal") for name in sorted(REGISTRY)]
                + [("rubato-128m", "alternating")])
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


@pytest.mark.parametrize("name,variant", KERNEL_CASES)
def test_keystream_kernel_compiles_for_v5e(topo, name, variant):
    p = get_params(name)
    one = SingleDeviceSharding(topo.devices[0])
    noise = (jax.ShapeDtypeStruct((BLK, p.l), jnp.int32, sharding=one)
             if p.n_noise else None)
    mats = (_u32((BLK, p.n_matrix_constants), one)
            if p.n_matrix_constants else None)
    compiled = keystream_kernel_apply.lower(
        p, _u32((p.n,), one), _u32((BLK, p.n_round_constants), one), noise,
        interpret=False, variant=variant, mats=mats).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_engine_compiles_without_collectives(topo):
    """The lane-sharded engine on a 4-chip mesh: one kernel per device, the
    key replicated, and no cross-device traffic."""
    p = get_params("rubato-128l")
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    eng = make_engine("sharded", p, jnp.arange(1, p.n + 1, dtype=jnp.uint32),
                      mesh=mesh)
    lanes = BLK * len(topo.devices)
    split = NamedSharding(mesh, P("data", None))
    rc = _u32((lanes, p.n_round_constants), split)
    noise = jax.ShapeDtypeStruct((lanes, p.l), jnp.int32, sharding=split)
    text = jax.jit(eng.keystream_from_constants).lower(
        rc, noise).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [c for c in COLLECTIVES if c in text]


def test_keystream_kernel_keeps_the_name_the_trace_readers_match(topo):
    """The chip benchmark's kernel_us_per_block and
    keystream_kernel_roofline sum the device ops whose name contains
    ``keystream_kernel``: the Pallas call is the HLO instruction named
    after ``keystream_kernel_apply``, which the device trace shows."""
    p = get_params("hera-80")
    one = SingleDeviceSharding(topo.devices[0])
    text = keystream_kernel_apply.lower(
        p, _u32((p.n,), one), _u32((BLK, p.n_round_constants), one),
        interpret=False).compile().as_text()
    calls = [ln.split("=")[0].strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all("keystream_kernel" in c for c in calls), calls
