"""Compile-only rehearsal of the chip path for a described TPU v5e.

The policy engine's main path on a TPU is the Pallas matcher launched
under ``shard_map`` over the device store's resident blocks. These tests
compile it — and the kernels under it — for a v5e that is described, not
attached, at the store's real widths: 21 block rows (16 kernel columns,
validity, 4 analytics rows), tile 1024, a 4-program purge policy padded
to 16 instructions. Nothing runs; the TPU compiler refuses here what the
chip would refuse (unlowerable primitives, VMEM overruns).

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.catalog import StringTable
from repro.core.device_store import _N_ANALYTICS, _VALID_COL
from repro.core.policy import KERNEL_COLUMNS, compile_programs, parse_expr
from repro.kernels.policy_scan.ref import OP_NOP

TILE = 1024
BLOCK_ROWS = len(KERNEL_COLUMNS) + 1 + _N_ANALYTICS        # 21
ROWS = 256 * TILE                                          # per device
N_INSTR = 16
SIZE_COL = KERNEL_COLUMNS.index("size")
BLOCKS_COL = KERNEL_COLUMNS.index("blocks")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache: keep it out while these tests compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _purge_programs():
    """The (R=4, P=16) program batch of a 3-rule purge policy: combined
    criteria + size / age / owner rules, as ``compile_programs`` emits
    it, OP_NOP-padded to 16 instructions."""
    strings = StringTable()
    for u in ("user0", "user1", "user2"):
        strings.intern(u)
    rules = ["size > 1GB", "last_access > 30d",
             "owner == 'user2' and size > 1MB"]
    combined = "type == file and (" + " or ".join(
        f"({r})" for r in rules) + ")"
    ops, colidx, _ = compile_programs(
        [parse_expr(e) for e in [combined] + rules], strings, now=1e9)
    assert ops.shape[1] <= N_INSTR
    pad = ((0, 0), (0, N_INSTR - ops.shape[1]))
    ops = np.pad(ops, pad, constant_values=OP_NOP)
    colidx = np.pad(colidx, pad)
    return (tuple(tuple(int(o) for o in r) for r in ops),
            tuple(tuple(int(c) for c in r) for r in colidx))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_programs", [1, 4])
def test_policy_scan_batch_pallas_compiles(one_chip, no_compile_cache,
                                           n_programs):
    """The whole purge batch, and its combined criteria alone (the launch
    of the one-program ``policy_scan`` op)."""
    from repro.kernels.policy_scan.kernel import policy_scan_batch_pallas
    ops_t, colidx_t = (t[:n_programs] for t in _purge_programs())
    cols = jax.ShapeDtypeStruct((BLOCK_ROWS, ROWS), jnp.float32,
                                sharding=one_chip)
    operands = jax.ShapeDtypeStruct((len(ops_t), N_INSTR), jnp.float32,
                                    sharding=one_chip)
    _compile(lambda c, o: policy_scan_batch_pallas(
        c, o, ops_t=ops_t, colidx_t=colidx_t, size_col=SIZE_COL,
        blocks_col=BLOCKS_COL, valid_col=_VALID_COL, tile=TILE,
        interpret=False), cols, operands)


@pytest.mark.parametrize("n_rows", [7, BLOCK_ROWS])
def test_profile_cube_pallas_compiles_at_max_groups(one_chip,
                                                    no_compile_cache,
                                                    n_rows):
    """At the cap the (B, tile) one-hots still fit the kernel's VMEM, both
    in the op's 7-row layout and over a full store block."""
    from repro.kernels.profile_cube.kernel import profile_cube_pallas
    from repro.kernels.profile_cube.ops import MAX_GROUPS
    assert MAX_GROUPS % 8 == 0        # the op pads groups to 8: no overrun
    cols = jax.ShapeDtypeStruct((n_rows, ROWS), jnp.float32,
                                sharding=one_chip)
    _compile(lambda c: profile_cube_pallas(
        c, n_groups=MAX_GROUPS, gid_col=0, size_col=1, blocks_col=2,
        age_col=3, sb_col=4, ab_col=5, valid_col=6, tile=TILE,
        interpret=False), cols)


@pytest.mark.parametrize("scoped", [False, True])
def test_mesh_policy_scan_batch_compiles_with_kernel(topo, no_compile_cache,
                                                     scoped):
    """The store's match launch on a one-chip mesh takes the kernel: the
    compiled program holds the Mosaic custom call (no oracle fallback)."""
    from repro.kernels.policy_scan.ops import mesh_policy_scan_batch
    ops_t, colidx_t = _purge_programs()
    mesh = Mesh(np.asarray(topo.devices[:1]), ("shards",))
    sharded = NamedSharding(mesh, P("shards"))
    cols = jax.ShapeDtypeStruct((1, BLOCK_ROWS, ROWS), jnp.float32,
                                sharding=sharded)
    operands = jax.ShapeDtypeStruct((len(ops_t), N_INSTR), jnp.float32,
                                    sharding=NamedSharding(mesh, P()))
    kw = dict(mesh=mesh, ops_t=ops_t, colidx_t=colidx_t, size_col=SIZE_COL,
              blocks_col=BLOCKS_COL, valid_col=_VALID_COL, use_kernel=True,
              tile=TILE, with_agg=False)
    args = (cols, operands)
    if scoped:
        kw["perm"] = jax.ShapeDtypeStruct((1, 8, ROWS // 32), jnp.uint32,
                                          sharding=sharded)
        kw["subject"] = jax.ShapeDtypeStruct((), jnp.int32,
                                             sharding=NamedSharding(mesh,
                                                                    P()))
    compiled = mesh_policy_scan_batch.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_report_ops_compile(topo, no_compile_cache):
    """find/top-N/du's resident launches compile for the chip too."""
    from repro.kernels.policy_scan.ops import (mesh_column_topk,
                                               mesh_range_aggregate,
                                               mesh_threshold_rows)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("shards",))
    sharded = NamedSharding(mesh, P("shards"))
    cols = jax.ShapeDtypeStruct((1, BLOCK_ROWS, ROWS), jnp.float32,
                                sharding=sharded)
    type_col = KERNEL_COLUMNS.index("type")
    filt = dict(valid_col=_VALID_COL, type_col=type_col, file_code=1.0)
    mesh_column_topk.lower(cols, mesh=mesh, col=SIZE_COL, k=64,
                           **filt).compile()
    thr = jax.ShapeDtypeStruct((), jnp.float32,
                               sharding=NamedSharding(mesh, P()))
    mesh_threshold_rows.lower(cols, thr, mesh=mesh, col=SIZE_COL,
                              **filt).compile()
    bounds = jax.ShapeDtypeStruct((1, 4), jnp.float32, sharding=sharded)
    mesh_range_aggregate.lower(cols, bounds, mesh=mesh,
                               ord_col=_VALID_COL + 1, type_col=type_col,
                               size_col=SIZE_COL, blocks_col=BLOCKS_COL,
                               valid_col=_VALID_COL).compile()
