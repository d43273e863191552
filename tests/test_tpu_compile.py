"""The Pallas kernels of the main path compile for a TPU v5e chip.

Nothing here needs a chip: the TPU compiler is asked to build each kernel
for a *described* v5e chip, at the widths the smoke run uses (bind_cap 2048,
four variables, an 8192-row KB block, out_cap 2048, a 512-class closure).
A kernel Mosaic refuses fails here instead of on the chip, which interpret
mode on the CPU can never show.  The topology is described inside a fixture
(never at import), and the persistent compilation cache is off around the
compiles: an entry built for a described chip cannot be read back.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pattern import CompiledPattern, Slot
from repro.kernels.closure import kernel as ck
from repro.kernels.hash_join import kernel as hk

BIND_CAP, NV, KB_ROWS, OUT_CAP, K_MAX, CLASSES = 2048, 4, 8192, 2048, 8, 512
PAT = CompiledPattern(Slot.bound(0), Slot.const_(7), Slot.free(1))
I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scan_shapes():
    return ([(BIND_CAP, NV), (BIND_CAP, 1)] + [(1, KB_ROWS)] * 4, [I32] * 6)


def _probe_shapes():
    return ([(BIND_CAP, NV), (BIND_CAP, 1)] + [(BIND_CAP, K_MAX)] * 4,
            [I32] * 6)


KERNELS = {
    # name: (function, (shapes, dtypes), kernel launches it holds)
    "match_matrix": (
        lambda *a: hk.match_matrix_pallas(*a, PAT, interpret=False),
        _scan_shapes(), ("hash_join_match",)),
    "join_compact": (
        lambda *a: hk.join_compact_pallas(*a, PAT, OUT_CAP, interpret=False),
        _scan_shapes(), ("hash_join_count", "hash_join_emit")),
    "probe_compact": (
        lambda *a: hk.probe_compact_pallas(*a, PAT, OUT_CAP, interpret=False),
        _probe_shapes(), ("hash_join_probe",)),
    "descendants": (
        lambda r, c: ck.descendants_pallas(r, c, CLASSES, interpret=False),
        ([(CLASSES, CLASSES), (CLASSES,)], [F32, F32]),
        ("closure_descendants",)),
    "closure_step": (
        lambda r: ck.closure_step_pallas(r, interpret=False),
        ([(CLASSES, CLASSES)], [F32]), ("closure_step",)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, (shapes, dtypes), launches = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, dtypes)]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= len(launches), name
    for launch in launches:
        assert launch in text, (name, launch)
    # the kernels keep their working set on chip: the program's scratch in
    # HBM is a few MiB at most, nowhere near the 16 GB of one v5e chip
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
