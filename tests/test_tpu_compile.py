"""Compile the Pallas kernels for a described TPU v5e chip (no chip needed).

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tile, slices inside a tile, layouts XLA and Mosaic disagree on.
These tests lower each kernel at the serve path's widths for one chip of a
described ``v5e:2x2`` topology and check that the compiled program holds
the kernel itself (``tpu_custom_call``). Nothing runs; nothing is measured.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

N_ROWS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what the persistent cache stores
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("q8", [False, True], ids=["f32", "q8"])
@pytest.mark.parametrize("B,C,d", [(64, 48, 128), (16, 48, 960)])
def test_gather_kernels_compile_for_v5e(one_chip, q8, B, C, d):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kernel = ops.gather_scores_q8 if q8 else ops.gather_scores
    _compile(
        lambda t, n, i, q: kernel(t, n, i, q, interpret=False),
        spec((N_ROWS, d), jnp.int8 if q8 else jnp.float32),
        spec((N_ROWS,), jnp.float32),
        spec((B, C), jnp.int32),
        spec((B, d), jnp.float32),
    )


@pytest.mark.parametrize("kernel", ["score_matrix", "score_topk"])
def test_score_kernels_compile_for_v5e(one_chip, kernel):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    if kernel == "score_matrix":
        fn = lambda x, n, q: ops.score_matrix(x, n, q, interpret=False)  # noqa: E731
    else:
        fn = lambda x, n, q: ops.score_topk(x, n, q, 10, interpret=False)  # noqa: E731
    _compile(fn, spec((4096, 128)), spec((4096,)), spec((256, 128)))
