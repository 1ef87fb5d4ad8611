"""The simulator's device kernels compile for a TPU v5e at chip_smoke.py's
sizes, fit its 16 GiB of HBM, and keep the relay einsum in f32.

Compiles for a described ``v5e:2x2`` topology; nothing runs.  The topology
is described inside a module-scoped fixture, never while a module is
imported: only one process may load the TPU library, and the suite runs
with several workers.
"""
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core.simulator import (  # noqa: E402
    jax_kernels,
    kernel_abstract_inputs,
)

HBM_BYTES = 16 << 30

# chip_smoke.py's buckets (its phase lines print them as kernels=...):
# sweep n=256 (singlehop, twohop_dense), two-hop n=64 and n=512, where
# ns = the capacity LUT rows, the sum of the cases' period slots, and
# (D, P) = twohop_sparse's slots per row and plans
SMOKE_SIZES = {
    "singlehop": dict(B=6, n=256, H_pad=1024, K=352, Jtot=4928),
    "twohop_dense": dict(B=4, n=256, H_pad=1024, K=256, ns=256),
    "twohop_fct": dict(B=4, n=64, H_pad=1024, K=96, ns=64),
    "twohop_sparse": dict(B=2, n=512, H_pad=384, K=320, D=4, P=128),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it cannot be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """Compile each kernel once, with the persistent cache off: an
    executable for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    done: dict = {}

    def get(kernel: str):
        if kernel not in done:
            specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                     for s in kernel_abstract_inputs(kernel,
                                                     **SMOKE_SIZES[kernel])]
            done[kernel] = jax_kernels()[kernel].lower(*specs).compile()
        return done[kernel]

    yield get
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", sorted(SMOKE_SIZES))
def test_kernel_compiles_within_hbm(compiled, kernel):
    mem = compiled(kernel).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
             - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, (kernel, total)


def test_twohop_dense_einsum_is_f32_on_chip(compiled):
    """Without ``precision=HIGHEST`` the TPU runs the relay offload einsum
    as one bf16 MXU pass and the aggregates leave the 1e-3 contract."""
    hlo = compiled("twohop_dense").as_text()
    assert "operand_precision={highest,highest}" in hlo
