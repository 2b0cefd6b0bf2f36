"""Ahead-of-time compiles of the served path's Pallas kernels for a
described TPU v5e, at the widths the served path launches them with
and at the blocks the autotuner picks for that chip.

Interpret mode runs the kernel bodies on the CPU but never shows what
Mosaic refuses (unsupported shape casts, VMEM, tiling). These cases do,
at no chip time: the TPU compiler compiles for a chip that is described
but not attached. Nothing runs, so they say nothing about results or
speed. The topology is described inside a fixture only, so collecting
this file never loads the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import autotune as at
from repro.kernels import topk_l2 as tk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A described-chip compile can be written to the persistent cache
    but never read back without the chip; keep these compiles out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _blocks(topo, kernel, m, n, d, k, dtype):
    plan = at.choose_plan(
        kernel, m, n, d, k, dtype=str(jnp.dtype(dtype)),
        kind=topo.devices[0].device_kind,
    )
    return {"bm": plan["bm"], "bn": plan["bn"], "bk": plan["bk"]}


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [8, 64])
def test_topk_l2_compiles(topo, one_chip, k):
    m, n, d = 512, 65_536, 4
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = tk.topk_l2.lower(
        s((m, d), jnp.float32),
        s((n, d), jnp.float32),
        s((n,), jnp.int32),
        s((m,), jnp.float32),
        k=k,
        interpret=False,
        **_blocks(topo, "topk_l2", m, n, d, k, jnp.float32),
    ).compile()
    _assert_kernel(compiled)


def test_leaf_topk_l2_compiles(topo, one_chip):
    r, c, d, k = 512, 2048, 4, 8
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = tk.leaf_topk_l2.lower(
        s((r, d), jnp.float32),
        s((r, c, d), jnp.float32),
        s((r, c), jnp.int32),
        s((r,), jnp.float32),
        k=k,
        interpret=False,
        **_blocks(topo, "leaf_topk_l2", r, c, d, k, jnp.float32),
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize(
    "r,c,d,dtype",
    [
        (512, 2048, 4, jnp.bfloat16),
        (512, 2048, 4, jnp.int8),
        (64, 1024, 768, jnp.bfloat16),  # retrieval-width keys
    ],
    ids=["bf16", "int8", "bf16-d768"],
)
def test_leaf_topk_l2_raw_compiles(topo, one_chip, r, c, d, dtype):
    k = 16  # k + the quantized over-fetch slack
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cscale = s((r, c), jnp.float32) if dtype == jnp.int8 else None
    compiled = tk.leaf_topk_l2_raw.lower(
        s((r, d), jnp.float32),
        s((r, c, d), dtype),
        s((r, c), jnp.int32),
        s((r,), jnp.float32),
        k=k,
        cscale=cscale,
        interpret=False,
        **_blocks(topo, "leaf_topk_l2", r, c, d, k, dtype),
    ).compile()
    _assert_kernel(compiled)
