"""The three paged Pallas kernels compile for a TPU v5e at serving widths.

Interpret mode (the other kernel suites) checks numerics but not what the
TPU lowering accepts: block-shape tiling rules, VMEM use, layouts. These
tests lower each kernel with ``interpret=False`` against a *described*
v5e chip (no chip attached) at yi-6b's attention widths — head_dim 128,
G = 32 q / 4 kv heads = 8, page_size 16 — in bf16 and with the int8 pool;
the decode kernel also at internlm2-1.8b's (G = 16 q / 8 kv heads = 2) and
at the benchmark's slot counts.

The topology is described inside a module fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker running this
file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import flash_decode_fwd
from repro.kernels.prefill_attention.kernel import flash_prefill_fwd
from repro.kernels.verify_attention.kernel import flash_verify_fwd

H, KV, HD, PS = 32, 4, 128, 16        # yi-6b attention widths
SLOTS, NPAGES = 8, 64                 # 8 slots x 1024 tokens
POOL_PAGES = SLOTS * NPAGES + 1       # + the sink page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # Lowering here writes nothing worth keeping to a persistent cache, and
    # reading such entries back without a chip only warns.
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _pool_specs(sharding, kv_dtype, kv=KV, pool_pages=POOL_PAGES):
    """Abstract (k, v, scale kwargs) for one layer of the paged pool."""
    shape = (kv, pool_pages, PS, HD)
    s = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=sharding)
    if kv_dtype == "int8":
        scale = s((kv, pool_pages, 1, PS), jnp.float32)
        return (s(shape, jnp.int8), s(shape, jnp.int8),
                dict(k_scale=scale, v_scale=scale))
    return s(shape, jnp.bfloat16), s(shape, jnp.bfloat16), {}


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# The benchmark's decode shapes: (slots, q heads, kv heads), 96-page tables.
DECODE_SHAPES = {"yi-6b": (16, 32, 4), "internlm2-1.8b": (32, 16, 8)}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model,pages_per_block",
                         [("yi-6b", None), ("internlm2-1.8b", None),
                          ("yi-6b", 1)])
def test_decode_kernel_compiles(one_chip, model, pages_per_block, kv_dtype):
    """The block kernel at its chosen block size, at both head layouts
    (G = 8 and G = 2), and at a block of one page: 16 rows, whose int8
    scale row is narrower than a lane tile."""
    slots, h, kv = DECODE_SHAPES[model]
    npages = 96
    k, v, scales = _pool_specs(one_chip, kv_dtype, kv=kv,
                               pool_pages=slots * npages + 1)
    i32 = lambda shp: jax.ShapeDtypeStruct(shp, jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((slots, h, HD), jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v, pt, ln, sc: flash_decode_fwd(
        q, k, v, pt, ln, pages_per_block=pages_per_block, **sc),
        q, k, v, i32((slots, npages)), i32((slots,)), scales)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_prefill_kernel_compiles(one_chip, kv_dtype):
    chunk = 32
    k, v, scales = _pool_specs(one_chip, kv_dtype)
    i32 = lambda shp: jax.ShapeDtypeStruct(shp, jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((2, chunk, H, HD), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v, pt, qs, sc: flash_prefill_fwd(
        q, k, v, pt, qs, **sc),
        q, k, v, i32((2, NPAGES)), i32((2,)), scales)


@pytest.mark.parametrize("kv_dtype,num_splits",
                         [("bf16", 1), ("bf16", 4), ("int8", 1), ("int8", 4)])
def test_verify_kernel_compiles(one_chip, kv_dtype, num_splits):
    window = 5                        # K = 4 drafts + the current token
    k, v, scales = _pool_specs(one_chip, kv_dtype)
    i32 = lambda shp: jax.ShapeDtypeStruct(shp, jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((SLOTS, window, H, HD), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v, pt, pos, sc: flash_verify_fwd(
        q, k, v, pt, pos, num_splits=num_splits, **sc),
        q, k, v, i32((SLOTS, NPAGES)), i32((SLOTS,)), scales)
