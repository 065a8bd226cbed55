"""Row-addressed probe primitives shared by the store kernels.

Mosaic, the TPU kernel compiler, lowers no data-dependent vector gather:
`jnp.take` with computed indices inside a kernel is refused. What it does
lower is a load of whole 128-lane rows at a data-dependent ROW index (a
dynamic sublane offset), vector compares, and reductions back to a scalar.
So every store kernel walks ONE query at a time:

  * the query tile sits in SMEM; a `fori_loop` reads one query's scalars,
  * each probe step loads the 128-lane row(s) that hold the probed
    position, compares them against the query in one vector op, and
    reduces the outcome to a scalar (`first_lane`, `count`, `read_i32`),
  * that scalar addresses the next row; results go back to SMEM.

Flat planes are handed to the kernels as `[rows, 128]` rectangles
(`as_rows`), padded to at least two rows so a probe window of a few
positions can always be loaded as one aligned pair of rows.

Kernels are traced with 64-bit types off (`pallas_call`): the store runs
with `jax_enable_x64`, and Mosaic refuses the 64-bit constants and
accumulators that Python literals and `jnp.sum` would otherwise bring in.
For the same reason a reduction to a scalar never uses `jnp.sum` (whose
scalar lowering re-traces with a 64-bit accumulator): counts go through a
keep-dims sum and a max.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# widest query tile: Mosaic's 1-D block tiling for 32-bit arrays over 1024
# elements is 1024, so a longer batch is cut into 1024-query tiles
MAX_TILE = 1024
_I32_MIN = -(2 ** 31)


def as_rows(x: jnp.ndarray, fill) -> jnp.ndarray:
    """[..., N] plane -> [..., R, 128] rows (R >= 2), padded with `fill`.
    Positions keep their flat index: element p sits at row p // 128,
    lane p % 128. Host side (plain jnp, outside the kernel)."""
    n = x.shape[-1]
    r = max(-(-n // LANES), 2)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, r * LANES - n)]
    return jnp.pad(x, pad, constant_values=fill).reshape(
        x.shape[:-1] + (r, LANES))


def query_tiles(t: int, tile: int | None = None) -> tuple:
    """(padded length, tile) for a batch of t queries: one whole-array tile
    up to MAX_TILE (rounded up to 128), else MAX_TILE tiles."""
    tile = MAX_TILE if tile is None else tile
    if t <= tile:
        tp = -(-t // LANES) * LANES
        return tp, tp
    return -(-t // tile) * tile, tile


def smem_tile(tile: int) -> pl.BlockSpec:
    """A [tile] block of a query-indexed 1-D array, in SMEM."""
    return pl.BlockSpec((tile,), lambda g, *_: (g,),
                        memory_space=pltpu.SMEM)


def smem_whole(a) -> pl.BlockSpec:
    """The whole (small) array in SMEM, e.g. a run-boundary plane."""
    return pl.BlockSpec(a.shape, lambda g, *_: (0,) * a.ndim,
                        memory_space=pltpu.SMEM)


def vmem_whole(a) -> pl.BlockSpec:
    """The whole array, VMEM-resident for every grid step."""
    return pl.BlockSpec(a.shape, lambda g, *_: (0,) * a.ndim)


def pallas_call(kernel, *args, **kw):
    """`pl.pallas_call(kernel, **kw)(*args)`, traced with 64-bit types off
    (see the module docstring)."""
    with jax.enable_x64(False):
        return pl.pallas_call(kernel, **kw)(*args)


def for_each_query(n: int, body) -> None:
    """Run `body(i)` for i in [0, n): the per-query loop of a tile."""
    def step(i, carry):
        body(i)
        return carry
    jax.lax.fori_loop(0, n, step, 0)


def lane_iota(rows: int = 1) -> jnp.ndarray:
    """Flat in-window offsets of a [rows, 128] block: row * 128 + lane."""
    shape = (rows, LANES)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def load_rows(ref, p, n: int = 1, lead: tuple = ()):
    """The n rows starting at the row that holds flat position p (clamped
    so all n rows exist). Returns (block [n, 128], flat position of its
    first element)."""
    nrows = ref.shape[-2]
    r0 = jnp.clip(p // LANES, 0, nrows - n)
    return ref[lead + (pl.ds(r0, n), slice(None))], r0 * LANES


def count(mask: jnp.ndarray) -> jnp.ndarray:
    """Number of true lanes of a [1, N] mask, as an int32 scalar."""
    return jnp.max(jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True))


def first_lane(mask: jnp.ndarray, idx: jnp.ndarray, none: int):
    """Smallest `idx` where `mask` holds, `none` where it never does."""
    return jnp.min(jnp.where(mask, idx, none))


def any_lane(mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.max(mask.astype(jnp.int32)) > 0


def read_i32(ref, p, lead: tuple = ()):
    """The int32 element at flat position p of a rows-form ref."""
    row, base = load_rows(ref, p, 1, lead)
    return jnp.max(jnp.where(lane_iota() + base == p, row, _I32_MIN))


def read_u32(ref, p, lead: tuple = ()):
    """The u32 element at flat position p of a rows-form ref, as a [1, 1]
    vector (Mosaic moves no u32 value into a scalar register; a [1, 1]
    vector broadcasts against rows like a scalar)."""
    row, base = load_rows(ref, p, 1, lead)
    bits = jax.lax.bitcast_convert_type(row, jnp.int32)
    v = jnp.max(jnp.where(lane_iota() + base == p, bits, _I32_MIN),
                axis=1, keepdims=True)
    return jax.lax.bitcast_convert_type(v, jnp.uint32)
