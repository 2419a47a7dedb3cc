"""Public profile-cube op: packs columns, pads, dispatches kernel/oracle.

``profile_cube`` turns four aligned columns (dense group id, size, blocks,
age-in-seconds) into the (3, B, S, A) count/volume/spc_used cube in one
launch. Rows are padded to the tile with an all-invalid pad; the group
axis is padded to the sublane multiple and sliced back.

``mesh_profile_cube`` is the mesh-resident analogue: it consumes the
device store's sharded ``(D, n_cols, Rp)`` global column array under
``shard_map``, builds one partial cube per device from that device's
resident block (Pallas kernel or jnp oracle — no column ever moves), and
``psum``-combines the partials into the replicated merged cube. Both the
sharded partials (which stay resident for warm scatter-add maintenance)
and the combined cube come back; ``mesh_cube_combine`` re-runs just the
psum over already-resident partials after in-place updates.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..policy_scan.ops import _mesh_on_tpu
from .kernel import LANE, profile_cube_pallas
from .ref import A_BUCKETS, N_MEASURES, S_BUCKETS, profile_cube_ref

# The (B, tile) gid one-hots must fit the kernel's scoped VMEM (16 MiB on
# a v5e): 3264 is the largest multiple of 8 that compiles for one at the
# default tile, in the op's 7-row layout and over a full 21-row store
# block (tests/kernels/test_tpu_compile.py). Catalogs with more distinct
# (owner, group, type, hsm) combinations take the host groupby path (see
# core.profiles).
MAX_GROUPS = 3264


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("n_groups", "use_kernel", "tile",
                                   "prebucketed"))
def _profile_cube_jit(cols: jax.Array, n_groups: int, use_kernel: bool,
                      tile: int, prebucketed: bool) -> jax.Array:
    """cols: (5|7, N) f32 rows [gid, size, blocks, age, (sb, ab,) valid]."""
    n = cols.shape[1]
    valid_col = 6 if prebucketed else 4
    sb_col, ab_col = (4, 5) if prebucketed else (-1, -1)
    pad_n = (-n) % tile
    if pad_n:
        cols = jnp.pad(cols, ((0, 0), (0, pad_n)))    # pad rows read valid=0
    pad_b = (-n_groups) % 8                           # f32 sublane multiple
    bp = n_groups + pad_b
    if use_kernel:
        cube = profile_cube_pallas(cols, n_groups=bp, valid_col=valid_col,
                                   sb_col=sb_col, ab_col=ab_col,
                                   tile=tile, interpret=not _on_tpu())
        cube = cube.reshape(N_MEASURES, bp, S_BUCKETS, A_BUCKETS)
    else:
        cube = profile_cube_ref(cols, bp, valid_col=valid_col,
                                sb_col=sb_col, ab_col=ab_col)
    return cube[:, :n_groups]


def profile_cube(gid, size, blocks, age, n_groups: int, valid=None,
                 sb=None, ab=None, use_kernel: Optional[bool] = None,
                 tile: int = 8 * LANE) -> np.ndarray:
    """Fused bucketize + segment-reduce over aligned entry columns.

    Returns the (N_MEASURES, n_groups, S_BUCKETS, A_BUCKETS) f32 cube:
    measure 0 counts, 1 sums ``size``, 2 sums ``blocks``; rows land in
    ``[gid, size_profile_bucket(size), age_profile_bucket(age)]``.

    ``sb``/``ab`` (optional) are precomputed bucket-index columns: pass
    them when raw sizes/ages exceed the f32 integer range (~2**24), where
    the on-device cast could round a value across a bucket edge —
    ``core.profiles`` always does, so bucket assignment matches its int64
    tables exactly. ``use_kernel=None`` selects the Pallas kernel on TPU
    and the jitted scatter-add oracle elsewhere (the kernel stays
    exercised off-TPU via interpret mode in tests). Sums are f32 — exact
    for integer measures up to 2**24 per cell; the incremental host path
    in ``core.profiles`` keeps int64 precision end-to-end.
    """
    if n_groups > MAX_GROUPS:
        raise ValueError(f"n_groups={n_groups} exceeds the on-device cap "
                         f"{MAX_GROUPS}; use the host groupby path")
    n = len(np.asarray(gid))
    if n_groups <= 0 or n == 0:
        return np.zeros((N_MEASURES, max(n_groups, 0), S_BUCKETS, A_BUCKETS),
                        np.float32)
    if valid is None:
        valid = np.ones(n, np.float32)
    prebucketed = sb is not None and ab is not None
    parts = (gid, size, blocks, age, sb, ab, valid) if prebucketed \
        else (gid, size, blocks, age, valid)
    cols = jnp.stack([jnp.asarray(np.asarray(c), jnp.float32)
                      for c in parts], axis=0)
    if use_kernel is None:
        use_kernel = _on_tpu()
    return np.asarray(_profile_cube_jit(cols, n_groups, use_kernel, tile,
                                        prebucketed))


# -- mesh-resident partial cubes (device-store analytics plane) --------------

@partial(jax.jit, static_argnames=("mesh", "n_groups", "gid_col", "size_col",
                                   "blocks_col", "sb_col", "ab_col",
                                   "valid_col", "use_kernel", "tile"))
def mesh_profile_cube(global_cols: jax.Array, *, mesh, n_groups: int,
                      gid_col: int, size_col: int, blocks_col: int,
                      sb_col: int, ab_col: int, valid_col: int,
                      use_kernel: bool = False, tile: int = 8 * LANE
                      ) -> tuple:
    """Per-device partial cubes + psum-combined merge, all under shard_map.

    ``global_cols`` is the store's assembled ``(D, n_cols, Rp)`` f32 array
    sharded along ``"shards"`` — each device builds the cube of its own
    resident rows (gid/sb/ab ride as extra analytics rows of the block,
    bucketized exactly on the host at scatter time), then the partials
    combine via ``psum``. Returns ``(partials, combined)``:

    * ``partials``: (D, N_MEASURES, n_groups * S * A) f32, sharded along
      ``"shards"`` — one flat partial cube resident per device, kept by
      the store for O(dirty) signed scatter-add maintenance;
    * ``combined``: (N_MEASURES, n_groups, S, A) f32, replicated — the
      merged cube (callers round to int64; exactness holds while per-cell
      sums stay inside the f32 integer envelope, like the single-device
      kernel path).

    ``n_groups`` must be a multiple of 8 (the f32 sublane — the store
    allocates the group axis padded) and ``Rp`` a multiple of ``tile``.
    """
    from jax.sharding import PartitionSpec as P

    def _device(cols):
        c = cols[0]                              # (n_cols, Rp) local block
        if use_kernel:
            cube = profile_cube_pallas(
                c, n_groups=n_groups, gid_col=gid_col, size_col=size_col,
                blocks_col=blocks_col, age_col=size_col, valid_col=valid_col,
                sb_col=sb_col, ab_col=ab_col, tile=tile,
                interpret=not _mesh_on_tpu(mesh))
            cube = cube.reshape(N_MEASURES, n_groups, S_BUCKETS, A_BUCKETS)
        else:
            cube = profile_cube_ref(
                c, n_groups, gid_col=gid_col, size_col=size_col,
                blocks_col=blocks_col, age_col=size_col, valid_col=valid_col,
                sb_col=sb_col, ab_col=ab_col)
        combined = jax.lax.psum(cube, "shards")
        return cube.reshape(N_MEASURES, -1)[None], combined

    return jax.shard_map(_device, mesh=mesh, in_specs=(P("shards"),),
                         out_specs=(P("shards"), P()),
                         check_vma=False)(global_cols)


@partial(jax.jit, static_argnames=("mesh", "n_groups", "gid_col", "size_col",
                                   "blocks_col", "sb_col", "ab_col",
                                   "valid_col"))
def mesh_scoped_cube(global_cols: jax.Array, perm: jax.Array,
                     subject: jax.Array, *, mesh, n_groups: int,
                     gid_col: int, size_col: int, blocks_col: int,
                     sb_col: int, ab_col: int, valid_col: int) -> jax.Array:
    """Subject-scoped profile cube in one fused launch over resident rows.

    Unlike :func:`mesh_profile_cube` there are no resident scoped
    partials — scoping is per-query: each device unpacks the subject's
    row from its ``(1, Sp, W)`` packed ``uint32`` permission buffer
    (``perm``, sharded along ``"shards"``; ``subject`` a traced i32 id),
    ANDs it into the validity row, and bins only visible rows; partial
    cubes psum into the replicated (N_MEASURES, n_groups, S, A) f32 cube.
    """
    from jax.sharding import PartitionSpec as P

    def _device(cols, pm, sid):
        c = cols[0]                              # (n_cols, Rp) local block
        words = jax.lax.dynamic_index_in_dim(pm[0], sid, axis=0,
                                             keepdims=False)
        bits = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)[None, :]) \
            & jnp.uint32(1)
        vis = (bits != 0).reshape(-1)
        masked = jnp.where(vis, c[valid_col], 0.0)
        c2 = jnp.concatenate([c, masked[None]], axis=0)
        cube = profile_cube_ref(
            c2, n_groups, gid_col=gid_col, size_col=size_col,
            blocks_col=blocks_col, age_col=size_col, valid_col=c.shape[0],
            sb_col=sb_col, ab_col=ab_col)
        return jax.lax.psum(cube, "shards")

    return jax.shard_map(_device, mesh=mesh,
                         in_specs=(P("shards"), P("shards"), P()),
                         out_specs=P(), check_vma=False)(
                             global_cols, perm,
                             jnp.asarray(subject, jnp.int32))


@partial(jax.jit, static_argnames=("mesh",))
def mesh_cube_combine(partials: jax.Array, *, mesh) -> jax.Array:
    """psum the resident (D, N_MEASURES, B*S*A) sharded partial cubes into
    the replicated merged cube — the only data that moves is the cube
    itself (columns stay put), so a warm query after scatter-add updates
    costs one small collective."""
    from jax.sharding import PartitionSpec as P

    def _device(p):
        return jax.lax.psum(p[0], "shards")

    return jax.shard_map(_device, mesh=mesh, in_specs=(P("shards"),),
                         out_specs=P(), check_vma=False)(partials)
