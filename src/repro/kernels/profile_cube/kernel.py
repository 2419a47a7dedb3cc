"""Pallas TPU kernel: fused profile-cube segment reduction.

One grid walk over the columnar entry table replaces the scalar
``StatsAggregator`` fold (one python dict update per entry per report
dimension): each grid step holds a (n_cols, tile) block in VMEM,
bucketizes the tile's rows on-device (log-size bucket from static edges,
age bucket from ``now - atime`` ages precomputed on the host), and
accumulates the (B, S*A) segment sums for the three measures through the
MXU — the segment reduction is expressed as two one-hot matmuls
(``G (B, tile) @ SA (tile, S*A)``), the standard TPU scatter-add idiom.

The cube accumulator block (3*B, S*A) is revisited by every grid step
(standard Pallas reduction pattern): rows [0, B) are counts, [B, 2B)
volumes, [2B, 3B) spc_used.

VMEM budget: the gid one-hots are (B, tile) f32 — with the default
``tile=1024`` that is 4 MB each at B=1024 — so the op wrapper caps the
group axis at the largest B that compiles for a v5e (``ops.MAX_GROUPS``;
callers with more distinct (owner, group, type, hsm) combinations fall
back to the host groupby path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import (AGE_EDGE_VALS, A_BUCKETS, N_MEASURES, SIZE_EDGE_VALS,
                  S_BUCKETS)

LANE = 128


def _profile_cube_kernel(cols_ref, cube_ref, *, n_groups: int, gid_col: int,
                         size_col: int, blocks_col: int, age_col: int,
                         valid_col: int, sb_col: int, ab_col: int):
    step = pl.program_id(0)
    cols = cols_ref[...]                      # (n_cols, tile) f32 in VMEM
    tile = cols.shape[1]

    gid = cols[gid_col]
    size = cols[size_col]
    blocks = cols[blocks_col]
    age = cols[age_col]
    valid = cols[valid_col] if valid_col >= 0 \
        else jnp.ones((tile,), jnp.float32)

    # --- bucketization ----------------------------------------------------
    # fused on-device from raw size/age, or taken from precomputed bucket
    # columns (exact host bucketization: raw values near a bucket edge
    # can round across it under the f32 cast; small indices are exact)
    if sb_col >= 0:
        sb = cols[sb_col].astype(jnp.int32)
    else:
        sb = sum((size >= e).astype(jnp.int32) for e in SIZE_EDGE_VALS) - 1
    sb = jnp.clip(sb, 0, S_BUCKETS - 1)
    if ab_col >= 0:
        ab = cols[ab_col].astype(jnp.int32)
    else:
        ab = sum((age >= e).astype(jnp.int32) for e in AGE_EDGE_VALS) - 1
    ab = jnp.clip(ab, 0, A_BUCKETS - 1)
    sa = sb * A_BUCKETS + ab                  # (tile,) i32

    # --- one-hot segment reduction through the MXU ------------------------
    # Mosaic builds iotas of integers only: compare the (exact, small)
    # group codes as int32
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (n_groups, tile), 0)
    onehot_g = (gid.astype(jnp.int32)[None, :] == iota_b) \
        .astype(jnp.float32) * valid[None, :]  # (B, tile)
    n_sa = S_BUCKETS * A_BUCKETS
    iota_sa = jax.lax.broadcasted_iota(jnp.int32, (n_sa, tile), 0)
    onehot_sa = (sa[None, :] == iota_sa).astype(jnp.float32)   # (SA, tile)

    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    count = dot(onehot_g, onehot_sa)                          # (B, SA)
    volume = dot(onehot_g * size[None, :], onehot_sa)         # (B, SA)
    spc = dot(onehot_g * blocks[None, :], onehot_sa)          # (B, SA)
    cube = jnp.concatenate([count, volume, spc], axis=0)      # (3B, SA)

    @pl.when(step == 0)
    def _init():
        cube_ref[...] = jnp.zeros_like(cube_ref)

    cube_ref[...] += cube


def profile_cube_pallas(cols: jax.Array, *, n_groups: int, gid_col: int = 0,
                        size_col: int = 1, blocks_col: int = 2,
                        age_col: int = 3, valid_col: int = -1,
                        sb_col: int = -1, ab_col: int = -1,
                        tile: int = 8 * LANE, interpret: bool = True
                        ) -> jax.Array:
    """cols: (n_cols, N) f32, N % tile == 0. Returns the
    (N_MEASURES * n_groups, S_BUCKETS * A_BUCKETS) f32 cube."""
    n_cols, n = cols.shape
    assert n % tile == 0, f"N={n} must be padded to tile={tile}"
    grid = (n // tile,)
    n_sa = S_BUCKETS * A_BUCKETS

    kernel = functools.partial(
        _profile_cube_kernel, n_groups=n_groups, gid_col=gid_col,
        size_col=size_col, blocks_col=blocks_col, age_col=age_col,
        valid_col=valid_col, sb_col=sb_col, ab_col=ab_col)

    cube = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_cols, tile), lambda i: (0, i)),   # column tile
        ],
        out_specs=pl.BlockSpec((N_MEASURES * n_groups, n_sa),
                               lambda i: (0, 0)),             # accumulator
        out_shape=jax.ShapeDtypeStruct((N_MEASURES * n_groups, n_sa),
                                       jnp.float32),
        interpret=interpret,
    )(cols)
    return cube
