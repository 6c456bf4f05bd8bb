"""TPU compile checks for the main-path Pallas kernels at real widths.

Every kernel is lowered and compiled by the TPU compiler for a described
(not attached) v5e chip at starcoder2_3b's attention layout: 24 query
heads, 2 KV heads, head_dim 128, 128-token blocks, B = 2.  Nothing runs;
a pass proves Mosaic accepts the kernel's block shapes, memory spaces and
VMEM budget, which interpret-mode tests cannot show.  Each kernel must
also be a ``tpu_custom_call`` instruction under its stable ``pallas_call``
name, which a profiler trace then shows as the operation's name.

The topology is described inside a module-scoped fixture only, never at
import: one process at a time may load the TPU library, and the test
workers must all collect the same tests.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import doc_attention as da
from repro.kernels.flash_decode import flash_decode, flash_decode_paged

B, HQ, HKV, D, T, BLK = 2, 24, 2, 128, 2048, 128
NB = T // BLK


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(chip, name, fn, *shapes):
    """Compile ``fn`` and check that the kernel ``name`` is in it as a
    custom call named ``%<name>`` or ``%<name>.N``."""
    lowered = jax.jit(fn).lower(*(_spec(chip, s, d) for s, d in shapes))
    hlo = lowered.compile().as_text()
    assert re.search(rf'%{name}(\.\d+)? = [^\n]*custom_call_target='
                     r'"tpu_custom_call"', hlo), f"no kernel named {name}"
    return hlo


def _tables(grid, steps):
    """Visit-table shapes: rect (idx (B, NB, V), nvis (B, NB)), flat
    (row, col, flags) each (B, steps)."""
    i32 = jnp.int32
    if grid == "rect":
        return [((B, NB, NB), i32), ((B, NB), i32)]
    return [((B, steps), i32)] * 3


QKV = [((B, HQ, T, D), jnp.bfloat16), ((B, HKV, T, D), jnp.bfloat16),
       ((B, HKV, T, D), jnp.bfloat16)]
META = [((B, T), jnp.int32)] * 4
STATS = [((B, HQ, T), jnp.float32)] * 2
GRIDS = ["flat", "rect"]
# causal visit count of an NB x NB block grid: the flat queue's length
STEPS = NB * (NB + 1) // 2


@pytest.mark.parametrize("grid", GRIDS)
def test_flash_fwd_compiles(chip, grid):
    def fwd(q, k, v, qd, qp, kd, kp, *tabs):
        return da.flash_fwd(q, k, v, qd, qp, kd, kp, tabs, scale=D ** -0.5,
                            block_q=BLK, block_k=BLK, grid=grid)

    _compile(chip, "doc_attn_fwd", fwd, *QKV, *META, *_tables(grid, STEPS))


@pytest.mark.parametrize("grid", GRIDS)
def test_flash_bwd_dq_compiles(chip, grid):
    def dq(q, k, v, do, lse, delta, qd, qp, kd, kp, *tabs):
        return da.flash_bwd_dq(q, k, v, do, lse, delta, qd, qp, kd, kp,
                               tabs, scale=D ** -0.5, block_q=BLK,
                               block_k=BLK, grid=grid)

    _compile(chip, "doc_attn_dq", dq, *QKV, QKV[0], *STATS, *META,
             *_tables(grid, STEPS))


@pytest.mark.parametrize("grid", GRIDS)
def test_flash_bwd_dkv_compiles(chip, grid):
    def dkv(q, k, v, do, lse, delta, qd, qp, kd, kp, *tabs):
        return da.flash_bwd_dkv(q, k, v, do, lse, delta, qd, qp, kd, kp,
                                tabs, scale=D ** -0.5, block_q=BLK,
                                block_k=BLK, grid=grid)

    _compile(chip, "doc_attn_dkv", dkv, *QKV, QKV[0], *STATS, *META,
             *_tables(grid, STEPS))


@pytest.mark.parametrize("partial", [False, True])
def test_flash_decode_compiles(chip, partial):
    S = 4096
    fn = functools.partial(flash_decode, block_k=256, partial=partial)
    _compile(chip, "flash_decode", fn, ((8, HQ, D), jnp.bfloat16),
             ((8, HKV, S, D), jnp.bfloat16), ((8, HKV, S, D), jnp.bfloat16),
             ((8,), jnp.int32))


def test_flash_decode_paged_compiles(chip):
    bs, blocks, per_req = 16, 2048, 256
    fn = functools.partial(flash_decode_paged, block_size=bs)
    _compile(chip, "flash_decode_paged", fn, ((8, HQ, D), jnp.bfloat16),
             ((HKV, blocks * bs, D), jnp.bfloat16),
             ((HKV, blocks * bs, D), jnp.bfloat16),
             ((8,), jnp.int32), ((8, per_req), jnp.int32))
