"""Compile the Pallas kernels for a described TPU v5e at the sizes the main
path uses: the Fig. 1 pipeline at 10M rows and codeqwen1.5-7b prefill.

Nothing runs, so these tests say nothing about results or times; they catch
what the chip's compiler refuses (block tiling, VMEM) and interpret mode
cannot see. The topology is described inside a fixture, never while a module
is imported: one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import filter_compact, flash_attention, groupby_agg, ops

PIPELINE_ROWS = 10_000_000     # the Fig. 1 source table at paper scale
SHARD_ROWS = PIPELINE_ROWS // 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _padded(shape_fn, n, dtype):
    """The shape ops.py hands a kernel for an n-row input."""
    return jax.eval_shape(shape_fn, jax.ShapeDtypeStruct((n,), dtype)).shape


def _compile_for_chip(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", [filter_compact.block_counts,
                                    filter_compact.block_compact])
def test_filter_kernels_compile_at_pipeline_scale(one_chip, kernel):
    mask = _padded(lambda m: ops._pad_to(m.astype(jnp.int32), ops.ROW_BLOCK,
                                         0)[None, :],
                   PIPELINE_ROWS, jnp.bool_)
    assert mask == (1, -(-PIPELINE_ROWS // ops.ROW_BLOCK) * ops.ROW_BLOCK)
    _compile_for_chip(lambda m: kernel(m, ops.ROW_BLOCK, interpret=False),
                      (mask, jnp.int32), sharding=one_chip)


@pytest.mark.parametrize("fn", ["sum", "count", "min", "max"])
@pytest.mark.parametrize("n_groups", [6, ops.MAX_GROUPS])
def test_groupby_kernel_compiles(one_chip, n_groups, fn):
    (rows,) = _padded(lambda v: ops._pad_to(v, ops.ROW_BLOCK, 0.0),
                      SHARD_ROWS, jnp.float32)
    ng_pad = ops._lane_pad(n_groups)
    _compile_for_chip(
        lambda v, c: groupby_agg.groupby_pallas(v, c, ng_pad, fn,
                                                ops.ROW_BLOCK,
                                                interpret=False),
        ((rows,), jnp.float32), ((rows,), jnp.int32), sharding=one_chip)


def test_combine_kernel_compiles(one_chip):
    _compile_for_chip(lambda p: groupby_agg.combine_pallas(p, "sum", 8,
                                                           interpret=False),
                      ((4, 128), jnp.float32), sharding=one_chip)


def test_flash_attention_compiles_at_codeqwen_prefill(one_chip):
    # batch 1 x 32 heads, 4096 tokens, head_dim 128 (codeqwen1.5-7b)
    qkv = ((32, 4096, 128), jnp.bfloat16)
    _compile_for_chip(
        lambda q, k, v: flash_attention.flash_attention_3d(
            q, k, v, causal=True, interpret=False),
        qkv, qkv, qkv, sharding=one_chip)
