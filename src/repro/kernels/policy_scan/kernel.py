"""Pallas TPU kernel: fused columnar predicate scan + aggregation.

The TPU-native analogue of Robinhood's MySQL table scan (paper C1) fused
with its on-the-fly aggregation (C6): one pass through the entry table
evaluates a policy's postfix predicate programs and accumulates count /
volume / spc_used / size-profile histogram — without materializing
intermediate masks in HBM.

Tiling: the entry table is columnar f32[n_cols, N]; the grid walks row
tiles of ``tile`` entries (lane-dim aligned to 128). Each grid step holds a
(n_cols, tile) block in VMEM, evaluates every program on the tile, writes
the tile's (R, tile) mask block and first-match-wins rule attribution, and
accumulates the per-program aggregates into an (R, N_AGG) accumulator
block (revisited by every grid step — standard Pallas reduction pattern).

Where the program lives: its *structure* — the opcode and column of every
instruction (``ops_t``/``colidx_t``) — is static Python data baked into
the kernel at trace time, so each instruction lowers to the one compare it
needs on one column row of the VMEM tile, and the AND/OR/NOT stack exists
only while tracing (no dynamic indexing, no scatter: what Mosaic lowers).
Only the operand values (``now``-relative thresholds) are data: the (R, P)
f32 operand table rides whole in SMEM and each compare reads its
threshold as a scalar, so a policy re-runs with a new ``now`` without
recompiling. One compilation per policy *shape*.

:func:`policy_scan_batch_pallas` runs the full (R, P) program batch of a
policy (combined criteria + per-rule conditions) in a SINGLE launch — one
grid walk over the entry table replaces R launches plus two host-side
passes; a single program is the R = 1 case.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import N_AGG, OP_AND, OP_LE, OP_NOP, OP_NOT

LANE = 128
# static python floats (array constants cannot be captured by a kernel)
_EDGE_VALS = (0.0, 1.0, 32.0, float(1 << 10), float(32 << 10),
              float(1 << 20), float(32 << 20), float(1 << 30),
              float(32 << 30), float(1 << 40))
_HIST0 = 3                       # agg slot of size-profile bucket 0
# compare opcodes OP_EQ..OP_LE, in opcode order
_CMPS = (jnp.equal, jnp.not_equal, jnp.greater, jnp.greater_equal,
         jnp.less, jnp.less_equal)

Program = Tuple[Tuple[int, ...], ...]


def _eval_program_tile(cols_ref, ops, colidx, operand):
    """One static postfix program over the (n_cols, tile) VMEM block.

    ``ops``/``colidx`` are python ints (OP_NOP padded); ``operand(i)``
    reads instruction i's threshold scalar from SMEM. Returns the (1, tile)
    f32 0/1 mask — the same arithmetic as ``ref.eval_program`` (compare ->
    f32, AND = product, OR = clipped sum, NOT = 1 - a), so interpret mode
    is bit-identical to the oracle on well-formed programs.
    """
    stack = []
    for i, (op, col) in enumerate(zip(ops, colidx)):
        if op == OP_NOP:
            continue
        if op <= OP_LE:
            row = cols_ref[col:col + 1, :]
            stack.append(_CMPS[op](row, operand(i)).astype(jnp.float32))
        elif op == OP_NOT:
            stack.append(1.0 - stack.pop())
        else:
            b, a = stack.pop(), stack.pop()
            stack.append(a * b if op == OP_AND
                         else jnp.clip(a + b, 0.0, 1.0))
    if not stack:
        return jnp.zeros((1, cols_ref.shape[1]), jnp.float32)
    return stack[-1]


def _policy_scan_kernel(operands_ref, cols_ref, masks_ref, rule_ref,
                        agg_ref, *, ops_t: Program, colidx_t: Program,
                        size_col: int, blocks_col: int, valid_col: int):
    """The whole (R, P) program batch over one column tile: the (R, tile)
    mask block, the fused first-match-wins rule attribution and the
    per-program aggregates.

    Program 0 is the policy's combined criteria; programs 1..R-1 are the
    per-rule conditions in priority order. Every loop is static, so the
    matcher lowers to straight-line compares and selects.
    """
    step = pl.program_id(0)
    tile = cols_ref.shape[1]
    n_progs = len(ops_t)
    for r in range(n_progs):
        mask = _eval_program_tile(cols_ref, ops_t[r], colidx_t[r],
                                  lambda i, r=r: operands_ref[r, i])
        if valid_col >= 0:
            mask = mask * cols_ref[valid_col:valid_col + 1, :]
        masks_ref[r:r + 1, :] = mask
    masks = masks_ref[...]                                 # (R, tile)

    # --- fused first-match-wins attribution (programs 1..R-1) -------------
    # walk the rules from lowest priority up: the last write is the first
    # rule that matched
    att = jnp.full((1, tile), -1, jnp.int32)
    for r in range(n_progs - 1, 0, -1):
        att = jnp.where(masks[r:r + 1] > 0.5, jnp.int32(r - 1), att)
    rule_ref[...] = att

    # --- fused per-program aggregation ------------------------------------
    size = cols_ref[size_col:size_col + 1, :]              # (1, tile)
    spc = cols_ref[blocks_col:blocks_col + 1, :]
    count = jnp.sum(masks, axis=1, keepdims=True)          # (R, 1)
    volume = jnp.sum(masks * size, axis=1, keepdims=True)
    spc_used = jnp.sum(masks * spc, axis=1, keepdims=True)
    any_match = jnp.max(masks, axis=1, keepdims=True)
    bucket = sum((size >= e).astype(jnp.int32) for e in _EDGE_VALS) - 1
    bucket = jnp.clip(bucket, 0, 9)
    # one-hot over the agg slots: row _HIST0 + b is set for bucket b, so
    # the matmul lands the histogram in its slots and zeros elsewhere
    slot = jax.lax.broadcasted_iota(jnp.int32, (N_AGG, tile), 0)
    onehot = (bucket + _HIST0 == slot).astype(jnp.float32)  # (N_AGG, tile)
    hist = jax.lax.dot_general(masks, onehot, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_progs, N_AGG), 1)
    agg = jnp.where(lane == 0, count,
                    jnp.where(lane == 1, volume,
                              jnp.where(lane == 2, spc_used, hist)))

    @pl.when(step == 0)
    def _init():
        agg_ref[...] = jnp.zeros_like(agg_ref)

    prev = agg_ref[...]
    # any_match is a max-, not sum-, accumulator
    agg_ref[...] = jnp.where(lane == N_AGG - 1,
                             jnp.maximum(prev, any_match), prev + agg)


def policy_scan_batch_pallas(cols: jax.Array, operands: jax.Array, *,
                             ops_t: Program, colidx_t: Program,
                             size_col: int = 0, blocks_col: int = 1,
                             valid_col: int = -1, tile: int = 8 * LANE,
                             interpret: bool = True
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """cols: (n_cols, N) f32, N % tile == 0; operands: (R, P) f32;
    ``ops_t``/``colidx_t``: the static (R, P) opcode/column tuples.

    Returns (masks (R, N) f32, rule_idx (N,) i32, agg (R, N_AGG) f32) from a
    single kernel launch.
    """
    n_cols, n = cols.shape
    assert n % tile == 0, f"N={n} must be padded to tile={tile}"
    n_progs = len(ops_t)
    kernel = functools.partial(
        _policy_scan_kernel, ops_t=ops_t, colidx_t=colidx_t,
        size_col=size_col, blocks_col=blocks_col, valid_col=valid_col)
    masks, rule, agg = pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # operands
            pl.BlockSpec((n_cols, tile), lambda i: (0, i)),      # columns
        ],
        out_specs=[
            pl.BlockSpec((n_progs, tile), lambda i: (0, i)),     # masks
            pl.BlockSpec((1, tile), lambda i: (0, i)),           # rule idx
            pl.BlockSpec((n_progs, N_AGG), lambda i: (0, 0)),    # aggregates
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_progs, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((n_progs, N_AGG), jnp.float32),
        ],
        interpret=interpret,
    )(operands.astype(jnp.float32), cols)
    return masks, rule[0], agg

