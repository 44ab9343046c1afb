"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The interpreter accepts layouts that Mosaic, the chip's kernel compiler,
refuses (unaligned lane slices, dynamic slices of values, scalar stores to
VMEM, working sets past the scoped VMEM limit). These tests compile each
kernel of the main path at real widths — D = 784 and a 200-class x 3-point
C-grid bank (B = 600) — for a described ``v5e:2x2`` topology, with no chip
attached, and check that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). Nothing runs, so they say nothing about results or
times.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and every test worker
imports this file. The kernels are steered to Mosaic with
``interpret=False``; off the chip the public wrappers would otherwise pick
interpret mode.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import gram, predict_bank, streamsvm_fit_many

D, B, N, Q = 784, 600, 4096, 512  # B = 200 classes x 3 C values


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single-device sharding on the described chip, with JAX's persistent
    compilation cache off: entries compiled for a described chip cannot be
    read back without one, and would only warn."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# The compiler's scoped VMEM grows with the stream length up to a point (it
# counts the stream tile's pipeline buffers at large N), so the two banks
# chip_smoke.py trains are also compiled at its N.
ENGINE = {
    "vmem-exact": dict(bank_resident="vmem"),
    "hbm-exact-derived-tile": dict(bank_resident="hbm"),
    "lookahead": dict(variant="lookahead", lookahead=4),
    "vmem-exact-bf16": dict(bank_resident="vmem", stream_dtype="bf16"),
    "auto-quickstart-n65536": dict(shape=(65536, 784, 600)),
    "hbm-beyond-vmem-n16384": dict(bank_resident="hbm",
                                   shape=(16384, 4096, 3000)),
    "one-model": dict(shape=(N, D, 1)),
}


@pytest.mark.parametrize("case", sorted(ENGINE))
def test_bank_engine_compiles_for_v5e(one_chip, case):
    kw = dict(ENGINE[case])
    n, d, b = kw.pop("shape", (N, D, B))
    text = _compiled_text(
        lambda X, Y, cs: streamsvm_fit_many(X, Y, cs, interpret=False, **kw),
        one_chip, (n, d), (b, n), (b,),
    )
    assert "tpu_custom_call" in text


PREDICT = {
    "scores": dict(),
    "ovr": dict(epilogue="ovr", n_classes=200),
    "topk": dict(epilogue="topk", k=5),
    "scores-hbm": dict(bank_resident="hbm"),
    "ovr-hbm-group-tiles": dict(
        epilogue="ovr", n_classes=200, b_tile=200, bank_resident="hbm"
    ),
    "scores-narrow-tiles": dict(b_tile=64),
}


@pytest.mark.parametrize("case", sorted(PREDICT))
def test_predict_bank_compiles_for_v5e(one_chip, case):
    kw = PREDICT[case]
    text = _compiled_text(
        lambda X, W: predict_bank(X, W, interpret=False, **kw),
        one_chip, (Q, D), (B, D),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("epilogue", ["linear", "rbf"])
def test_gram_compiles_for_v5e(one_chip, epilogue):
    text = _compiled_text(
        lambda A, Bm: gram(A, Bm, epilogue=epilogue, gamma=0.5, interpret=False),
        one_chip, (1000, D), (B, D),
    )
    assert "tpu_custom_call" in text


def _compile_four_chip_cell_fit(topo, stream_dtype):
    """``_sharded_fits`` compiled for the ImageNet-fc7 training deployment
    on a v5e 2x2 host: 1,281,164 rows x 4096 f32 split by rows over the
    four chips, B = 3000 (1000 classes x 3 C) in "auto" residency."""
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import _sharded_fits

    n, d, b = 1_281_164, 4096, 3000
    mesh = Mesh(np.array(topo.devices), ("data",))
    shape = lambda s, spec: jax.ShapeDtypeStruct(
        s, jnp.float32, sharding=NamedSharding(mesh, spec))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = _sharded_fits.lower(
            shape((n, d), P("data")), shape((b, n), P(None, "data")),
            shape((b,), P()),
            mesh=mesh, axes=("data",), n_rows=n, variant="exact",
            lookahead=None, block_n=256, b_tile=None,
            stream_dtype=stream_dtype, bank_resident="auto", interpret=False,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis()


def test_sharded_fit_of_the_four_chip_cell_reads_the_stream_in_place(topo):
    """The stream and signs are 9.09 GB a chip; the fit's own temporaries
    must stay under 1 GB a chip (a padded copy of the shard's stream and
    signs would be about 9 GB, past the chip's memory)."""
    mem = _compile_four_chip_cell_fit(topo, None)
    assert mem.temp_size_in_bytes < 1e9


def test_the_four_chip_cells_bf16_control_fits_the_chip(topo):
    """The cell's control, ``stream_dtype="bf16"``, copies the shard's
    stream and signs in bf16 (2.6 GB and 1.9 GB a chip; the compiler holds
    the signs' slice and their padded copy at once, 6.5 GB in all). It
    must compile next to the 9.09 GB of f32 data: copies of the signs in
    f32 would take 3.9 GB more."""
    mem = _compile_four_chip_cell_fit(topo, "bf16")
    assert mem.temp_size_in_bytes < 7e9
