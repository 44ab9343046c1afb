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

from repro.kernels import gram, predict_bank, streamsvm_fit, streamsvm_fit_many

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


def test_single_ball_engine_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda X, y: streamsvm_fit(X, y, 1.0, interpret=False),
        one_chip, (N, D), (N,),
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
