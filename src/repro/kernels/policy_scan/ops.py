"""Public policy-scan op: pads, dispatches kernel/oracle, unpads."""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import LANE, policy_scan_batch_pallas
from .ref import (N_AGG, OP_AND, OP_NOP, OP_NOT, OP_OR, aggregate_multi,
                  policy_scan_batch_ref, policy_scan_multi_ref)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mesh_on_tpu(mesh) -> bool:
    """Whether a mesh's devices are TPUs — the kernels compile for the
    chip there and run in interpret mode everywhere else (judged from the
    mesh itself, so a mesh of described TPU devices compiles the chip path
    from a CPU-only host)."""
    return mesh.devices.flat[0].platform == "tpu"


def policy_scan(cols: jax.Array, ops, colidx, operands: jax.Array,
                size_col: int = 0, blocks_col: int = 1, valid_col: int = -1,
                use_kernel: bool = True, tile: int = 8 * LANE
                ) -> Tuple[jax.Array, jax.Array]:
    """Evaluate a predicate program over a columnar table + aggregates.

    cols: (n_cols, N) f32; ops/colidx: concrete (P,) arrays (the program's
    structure is compiled in); operands: (P,) thresholds. Returns
    (mask (N,) f32, agg (N_AGG,) f32) — the one-program case of
    :func:`policy_scan_batch`.
    """
    masks, _rule, agg = policy_scan_batch(
        cols, np.asarray(ops)[None], np.asarray(colidx)[None],
        jnp.asarray(operands)[None], size_col=size_col,
        blocks_col=blocks_col, valid_col=valid_col, use_kernel=use_kernel,
        tile=tile)
    return masks[0], agg[0]


@partial(jax.jit, static_argnames=("size_col", "blocks_col"))
def policy_scan_multi(cols: jax.Array, ops: jax.Array, colidx: jax.Array,
                      operands: jax.Array, size_col: int = 0,
                      blocks_col: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Evaluate R padded predicate programs over one column stack.

    cols: (n_cols, N) f32; ops/colidx/operands: (R, P), OP_NOP padded.
    Returns (masks (R, N) f32, agg (N_AGG,) f32 for program 0). One
    columnar pass: matching and size/blocks aggregation fuse in one scan.
    """
    return policy_scan_multi_ref(cols, ops.astype(jnp.int32),
                                 colidx.astype(jnp.int32),
                                 operands.astype(jnp.float32),
                                 size_col=size_col, blocks_col=blocks_col)


def policy_scan_batch(cols: jax.Array, ops, colidx, operands: jax.Array,
                      size_col: int = 0, blocks_col: int = 1,
                      valid_col: int = -1, use_kernel: bool = True,
                      tile: int = 8 * LANE
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-launch batch matcher over a columnar table.

    cols: (n_cols, N) f32; ops/colidx: concrete (R, P) OP_NOP-padded
    opcode/column arrays (program 0 = combined criteria, 1..R-1 =
    per-rule conditions), compiled in as static structure; operands:
    (R, P) thresholds, traced. Returns (masks (R, N) f32, rule_idx (N,)
    i32, agg (R, N_AGG) f32): all program masks, fused first-match-wins
    attribution, and per-program size/blocks reductions — one kernel
    launch instead of R.
    """
    ops_t, colidx_t = _program_tuples(ops, colidx)
    return _policy_scan_batch(
        cols, jnp.asarray(operands, jnp.float32), ops_t=ops_t,
        colidx_t=colidx_t, size_col=size_col, blocks_col=blocks_col,
        valid_col=valid_col, use_kernel=use_kernel, tile=tile)


@partial(jax.jit, static_argnames=("ops_t", "colidx_t", "size_col",
                                   "blocks_col", "valid_col", "use_kernel",
                                   "tile"))
def _policy_scan_batch(cols, operands, *, ops_t, colidx_t, size_col,
                       blocks_col, valid_col, use_kernel, tile):
    n_cols, n = cols.shape
    if n == 0:            # zero-row table: nothing to scan (grid would be 0)
        r = len(ops_t)
        return (jnp.zeros((r, 0), jnp.float32), jnp.zeros((0,), jnp.int32),
                jnp.zeros((r, N_AGG), jnp.float32))
    pad = (-n) % tile
    if valid_col < 0:
        valid = jnp.ones((1, n), jnp.float32)
        cols = jnp.concatenate([cols, valid], axis=0)
        valid_col = n_cols
    if pad:
        cols = jnp.pad(cols, ((0, 0), (0, pad)))
    kw = dict(size_col=size_col, blocks_col=blocks_col, valid_col=valid_col)
    if use_kernel:
        masks, rule, agg = policy_scan_batch_pallas(
            cols, operands, ops_t=ops_t, colidx_t=colidx_t, tile=tile,
            interpret=not _on_tpu(), **kw)
    else:
        masks, rule, agg = policy_scan_batch_ref(
            cols, jnp.asarray(ops_t, jnp.int32),
            jnp.asarray(colidx_t, jnp.int32), operands, **kw)
    return masks[:, :n], rule[:n], agg


def _eval_unrolled(cols: jax.Array, ops: Tuple[int, ...],
                   colidx: Tuple[int, ...], operands: jax.Array) -> jax.Array:
    """Postfix program evaluation with the *program* static.

    The scan oracle treats the program as data: every instruction
    materializes a (6, N) comparison stack and a dynamically indexed
    (max_stack, N) value stack — ~10 full passes over the column tile per
    instruction, all memory bandwidth. A policy's opcode/column sequence
    is fixed per definition though (only the *operands* move with
    ``now``), so this path — like the Pallas kernel — unrolls the program
    in Python: each instruction lowers to exactly the one comparison it
    needs, the stack lives in tracer-land, and booleans (1 byte) replace
    f32 masks until the end.
    Bit-identical to :func:`repro.kernels.policy_scan.ref.eval_program` on
    {0, 1} masks — differential-tested.
    """
    stack: List[jax.Array] = []
    for i, op in enumerate(ops):
        if op == OP_NOP:
            continue
        if op < 6:
            vec = cols[colidx[i]]
            val = operands[i]
            # select the lambda BEFORE applying: one comparison traced per
            # instruction, not six
            cmp = (lambda a, b: a == b, lambda a, b: a != b,
                   lambda a, b: a > b, lambda a, b: a >= b,
                   lambda a, b: a < b, lambda a, b: a <= b)[op]
            stack.append(cmp(vec, val))
        elif op == OP_AND:
            b, a = stack.pop(), stack.pop()
            stack.append(a & b)
        elif op == OP_OR:
            b, a = stack.pop(), stack.pop()
            stack.append(a | b)
        elif op == OP_NOT:
            stack.append(~stack.pop())
    if not stack:
        return jnp.zeros(cols.shape[1], bool)
    return stack[-1]


def _unrolled_masks(cols: jax.Array, ops_t, colidx_t, operands: jax.Array,
                    valid_col: int) -> Tuple[List[jax.Array], jax.Array]:
    """Shared core of the unrolled paths: (bool program masks,
    first-match-wins rule_idx). Single semantics authority for the
    single-device oracle and the lean mesh branch — fix either behaviour
    here, never in a caller."""
    masks_b = []
    for r in range(len(ops_t)):
        m = _eval_unrolled(cols, ops_t[r], colidx_t[r], operands[r])
        if valid_col >= 0:
            m = m & (cols[valid_col] > 0.5)
        masks_b.append(m)
    if len(masks_b) > 1:
        rules = jnp.stack(masks_b[1:])
        first = jnp.argmax(rules, axis=0).astype(jnp.int32)
        rule = jnp.where(jnp.any(rules, axis=0), first, -1)
    else:
        rule = jnp.full(cols.shape[1], -1, jnp.int32)
    return masks_b, rule


@partial(jax.jit, static_argnames=("ops_t", "colidx_t", "size_col",
                                   "blocks_col", "valid_col"))
def policy_scan_batch_unrolled(cols: jax.Array, operands: jax.Array, *,
                               ops_t: Tuple[Tuple[int, ...], ...],
                               colidx_t: Tuple[Tuple[int, ...], ...],
                               size_col: int = 0, blocks_col: int = 1,
                               valid_col: int = -1
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Static-program batch matcher: the fast off-TPU single-launch path.

    Same contract as :func:`policy_scan_batch` — (masks (R, N) f32,
    rule_idx (N,) i32, agg (R, N_AGG) f32) — but the (R, P) opcode/column
    arrays are hashable tuples baked into the compilation (recompiles per
    policy *shape*, not per run: operand values, which carry ``now``-
    relative thresholds, stay dynamic). Needs no tile padding: there is no
    kernel grid, any N works.
    """
    masks_b, rule = _unrolled_masks(cols, ops_t, colidx_t, operands,
                                    valid_col)
    masks = jnp.stack(masks_b).astype(jnp.float32)
    agg = aggregate_multi(masks, cols[size_col], cols[blocks_col])
    return masks, rule, agg


def _program_tuples(ops: np.ndarray, colidx: np.ndarray
                    ) -> Tuple[Tuple[Tuple[int, ...], ...],
                               Tuple[Tuple[int, ...], ...]]:
    return (tuple(tuple(int(o) for o in row) for row in np.asarray(ops)),
            tuple(tuple(int(c) for c in row) for row in np.asarray(colidx)))


def _subject_bits(perm_local: jax.Array, sid: jax.Array) -> jax.Array:
    """Unpack one subject's packed visibility words into a per-row bool.

    ``perm_local`` is a device-local (Sp, W) uint32 permissions plane
    (one packed bitset row per subject, W = Rp // 32 words): bit ``b`` of
    word ``w`` — LSB-first — covers local row ``w * 32 + b``, matching
    the store's host-side ``np.packbits(..., bitorder="little")``
    staging. ``sid`` is a traced subject id (no recompile per subject).
    Returns the (W * 32,) bool visibility over the block's padded row
    axis — Rp is a tile multiple and the tile a multiple of 32, so the
    shapes line up exactly.
    """
    words = jax.lax.dynamic_index_in_dim(perm_local, sid, axis=0,
                                         keepdims=False)
    bits = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)[None, :]) \
        & jnp.uint32(1)
    return (bits != 0).reshape(-1)


@partial(jax.jit, static_argnames=("mesh", "ops_t", "colidx_t", "size_col",
                                   "blocks_col", "valid_col", "use_kernel",
                                   "tile", "with_agg"))
def mesh_policy_scan_batch(global_cols: jax.Array, operands: jax.Array, *,
                           mesh, ops_t: Tuple[Tuple[int, ...], ...],
                           colidx_t: Tuple[Tuple[int, ...], ...],
                           size_col: int = 0, blocks_col: int = 1,
                           valid_col: int = -1, use_kernel: bool = False,
                           tile: int = 8 * LANE, with_agg: bool = True,
                           perm: Optional[jax.Array] = None,
                           subject: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Data-parallel batch matcher over a device-resident sharded table.

    ``global_cols`` is (D, n_cols, Rp) f32, sharded along axis 0 over the
    1-D ``("shards",)`` mesh — one shard group's padded column stack per
    device, resident in device memory (see ``core.device_store``). Rp must
    be a tile multiple and ``valid_col`` must point at a 0/1 row-validity
    column (the store appends one), so no per-launch padding happens. The
    (R, P) opcode/column program structure rides as static tuples (only
    the replicated operand values are data — ``now``-relative thresholds
    change per run without recompiling).

    Under ``shard_map`` each device evaluates the whole program batch over
    its local (n_cols, Rp) block — the Pallas kernel
    (:func:`policy_scan_batch_pallas`, compiled for the chip when the
    mesh's devices are TPUs) when ``use_kernel`` else the unrolled
    static-program evaluator — with masks, first-match-wins attribution
    and per-program size/blocks reductions fused on-device; the
    per-program aggregates then combine across the mesh via ``psum``
    (``pmax`` for the any_match slot). Returns (mask0 (D, Rp) f32 and
    rule_idx (D, Rp) i32, both still sharded along ``"shards"``; agg
    (R, N_AGG) f32, replicated): only the combined-criteria mask and the
    attribution ever leave the devices — the column stack itself is never
    re-uploaded or gathered.

    ``with_agg=False`` takes a leaner unrolled path that skips the fused
    size-profile aggregation and the (R, N) f32 mask materialization
    entirely (returns a bool mask0 and a zero agg) — the policy engine's
    match path, which only consumes mask + attribution.

    ``perm``/``subject`` scope the whole match to one tenant: ``perm`` is
    the store's (D, Sp, W) uint32 permissions plane sharded along
    ``"shards"`` and ``subject`` a traced subject id. Each device unpacks
    its subject bitset row (:func:`_subject_bits`) and ANDs it into every
    program mask *before* attribution and aggregation — masks, rule_idx
    and the psum'd aggregates all come back visibility-filtered, exactly
    as if invisible rows were invalid.
    """
    from jax.sharding import PartitionSpec as P

    have_perm = perm is not None

    def _device_scan(cols, operands_, *rest):
        c = cols[0]
        bits = _subject_bits(rest[0][0], rest[1]) if have_perm else None
        if not use_kernel:
            masks_b, rule = _unrolled_masks(c, ops_t, colidx_t, operands_,
                                            valid_col)
            if bits is not None:
                masks_b = [m & bits for m in masks_b]
                rule = jnp.where(bits, rule, jnp.int32(-1))
            if with_agg:
                masks = jnp.stack(masks_b).astype(jnp.float32)
                agg = aggregate_multi(masks, c[size_col], c[blocks_col])
                mask0 = masks[0]
            else:
                agg = jnp.zeros((len(ops_t), N_AGG), jnp.float32)
                mask0 = masks_b[0]
        else:
            masks, rule, agg = policy_scan_batch_pallas(
                c, operands_, ops_t=ops_t, colidx_t=colidx_t,
                size_col=size_col, blocks_col=blocks_col,
                valid_col=valid_col, tile=tile,
                interpret=not _mesh_on_tpu(mesh))
            if bits is not None:
                # the kernel aggregated pre-AND: fold the subject bitset
                # into the masks and recompute the (cheap) reductions
                masks = masks * bits.astype(jnp.float32)
                rule = jnp.where(bits, rule, jnp.int32(-1))
                agg = aggregate_multi(masks, c[size_col], c[blocks_col])
            mask0 = masks[0]
        sums = jax.lax.psum(agg[:, : N_AGG - 1], "shards")
        anym = jax.lax.pmax(agg[:, N_AGG - 1:], "shards")
        return (mask0[None], rule[None],
                jnp.concatenate([sums, anym], axis=1))

    in_specs = (P("shards"), P()) + ((P("shards"), P()) if have_perm
                                     else ())
    args = (global_cols, operands.astype(jnp.float32))
    if have_perm:
        args = args + (perm, jnp.asarray(subject, jnp.int32))
    # check_vma=False: the agg output IS replicated — psum/pmax above
    # combine it across the mesh — but the Pallas call's outputs carry no
    # varying-axes type for the checker to prove it
    return jax.shard_map(
        _device_scan, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P("shards"), P("shards"), P()),
        check_vma=False,
    )(*args)


# -- mesh report ops (device-store-backed rbh-find / top-N / du) -------------
#
# These consume the same resident (D, n_cols, Rp) global column array as
# mesh_policy_scan_batch; only per-device top-k candidates, a row mask, or
# psum-combined aggregates ever leave the devices.

@partial(jax.jit, static_argnames=("mesh", "col", "k", "desc", "valid_col",
                                   "type_col", "file_code"))
def mesh_column_topk(global_cols: jax.Array, *, mesh, col: int, k: int,
                     desc: bool = True, valid_col: int = -1,
                     type_col: int = -1, file_code: float = 0.0,
                     perm: Optional[jax.Array] = None,
                     subject: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Per-device top-k over one column, restricted to valid FILE rows.

    Returns ``(vals (D, k) f32, idx (D, k) i32)``, both sharded along
    ``"shards"``: each device's k best (largest when ``desc``) column
    values and their local row indices. Rows failing the valid/type filter
    carry a ∓inf sentinel (callers drop non-finite candidates). The global
    top-k is a subset of the union of per-device top-k's, so the merged
    k-th best candidate value is an exact selection threshold for a
    follow-up :func:`mesh_threshold_rows` pass (which recovers boundary
    ties a per-device truncation could hide). ``perm``/``subject``
    (optional, see :func:`_subject_bits`) AND the subject's visibility
    bitset into the row filter — the scoped top-k ranks only rows the
    tenant may see.
    """
    from jax.sharding import PartitionSpec as P

    have_perm = perm is not None

    def _device(cols, *rest):
        c = cols[0]
        sel = c[valid_col] > 0.5
        if type_col >= 0:
            sel = sel & (c[type_col] == file_code)
        if have_perm:
            sel = sel & _subject_bits(rest[0][0], rest[1])
        sentinel = -jnp.inf if desc else jnp.inf
        key = jnp.where(sel, c[col], sentinel)
        vals, idx = jax.lax.top_k(key if desc else -key, k)
        vals = vals if desc else -vals
        return vals[None], idx[None].astype(jnp.int32)

    in_specs = (P("shards"),) + ((P("shards"), P()) if have_perm else ())
    args = (global_cols,) + ((perm, jnp.asarray(subject, jnp.int32))
                             if have_perm else ())
    return jax.shard_map(_device, mesh=mesh, in_specs=in_specs,
                         out_specs=(P("shards"), P("shards")),
                         check_vma=False)(*args)


@partial(jax.jit, static_argnames=("mesh", "col", "ge", "valid_col",
                                   "type_col", "file_code"))
def mesh_threshold_rows(global_cols: jax.Array, thr: jax.Array, *, mesh,
                        col: int, ge: bool = True, valid_col: int = -1,
                        type_col: int = -1, file_code: float = 0.0,
                        perm: Optional[jax.Array] = None,
                        subject: Optional[jax.Array] = None) -> jax.Array:
    """0/1 mask of valid FILE rows whose column value passes ``thr``.

    ``thr`` is a traced f32 scalar (no recompile per threshold). Returns
    the (D, Rp) f32 mask sharded along ``"shards"`` — the winning-row
    selection of the two-pass on-device top-k (see
    :func:`mesh_column_topk`); callers gather only the nonzero rows.
    ``perm``/``subject`` apply the same visibility AND as the top-k pass
    so both passes of a scoped query select from the same row set.
    """
    from jax.sharding import PartitionSpec as P

    have_perm = perm is not None

    def _device(cols, t, *rest):
        c = cols[0]
        sel = c[valid_col] > 0.5
        if type_col >= 0:
            sel = sel & (c[type_col] == file_code)
        if have_perm:
            sel = sel & _subject_bits(rest[0][0], rest[1])
        cmp = (c[col] >= t) if ge else (c[col] <= t)
        return (sel & cmp).astype(jnp.float32)[None]

    in_specs = (P("shards"), P()) + ((P("shards"), P()) if have_perm
                                     else ())
    args = (global_cols, jnp.asarray(thr, jnp.float32))
    if have_perm:
        args = args + (perm, jnp.asarray(subject, jnp.int32))
    return jax.shard_map(_device, mesh=mesh, in_specs=in_specs,
                         out_specs=P("shards"), check_vma=False)(*args)


@partial(jax.jit, static_argnames=("mesh", "ord_col", "type_col", "size_col",
                                   "blocks_col", "valid_col", "file_code"))
def mesh_range_aggregate(global_cols: jax.Array, bounds: jax.Array, *, mesh,
                         ord_col: int, type_col: int, size_col: int,
                         blocks_col: int, valid_col: int,
                         file_code: float = 0.0,
                         perm: Optional[jax.Array] = None,
                         subject: Optional[jax.Array] = None) -> jax.Array:
    """Fused subtree aggregate over sorted-path rank ranges, psum-combined.

    ``bounds`` is (D, 4) f32 sharded along ``"shards"``: per device the
    two half-open [lo, hi) ∪ [lo2, hi2) rank ranges (host binary searches
    into that group's sorted path mirror — the device-resident ``ord_col``
    holds each row's rank in that order). Returns the replicated (4,) f32
    ``[count, files, volume, spc_used]`` — ``du`` without any row leaving
    a device. ``perm``/``subject`` AND the subject's visibility bitset
    into the range mask — scoped ``du`` counts only rows the tenant may
    see, still in one fused pass.
    """
    from jax.sharding import PartitionSpec as P

    have_perm = perm is not None

    def _device(cols, b, *rest):
        c = cols[0]
        lo, hi, lo2, hi2 = b[0, 0], b[0, 1], b[0, 2], b[0, 3]
        o = c[ord_col]
        m = (c[valid_col] > 0.5) & (((o >= lo) & (o < hi))
                                    | ((o >= lo2) & (o < hi2)))
        if have_perm:
            m = m & _subject_bits(rest[0][0], rest[1])
        f = m & (c[type_col] == file_code)
        parts = jnp.stack([
            m.astype(jnp.float32).sum(),
            f.astype(jnp.float32).sum(),
            jnp.where(f, c[size_col], 0.0).sum(),
            jnp.where(f, c[blocks_col], 0.0).sum()])
        return jax.lax.psum(parts, "shards")

    in_specs = (P("shards"), P("shards")) + ((P("shards"), P())
                                             if have_perm else ())
    args = (global_cols, bounds.astype(jnp.float32))
    if have_perm:
        args = args + (perm, jnp.asarray(subject, jnp.int32))
    return jax.shard_map(_device, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(*args)


def column_stack(arrays) -> jax.Array:
    """Stack a Catalog.arrays() dict into the (n_cols, N) f32 kernel layout."""
    from ...core.policy import KERNEL_COLUMNS
    return jnp.stack([jnp.asarray(arrays[c], jnp.float32)
                      for c in KERNEL_COLUMNS], axis=0)


def _attribute_np(masks: List[np.ndarray]) -> np.ndarray:
    """Host-side first-match-wins attribution (per-rule-launch fallback):
    ``masks[0]`` is the combined criteria (excluded), ``masks[1:]`` the
    rules. Delegates to the single semantics authority in core.policy."""
    from ...core.policy import attribute_rules
    n = masks[0].shape[0] if masks else 0
    return attribute_rules(masks[1:], n)


def merge_agg_partials(parts: List[np.ndarray],
                       n_programs: int) -> np.ndarray:
    """Combine per-launch (R, N_AGG) aggregate blocks from a streamed /
    tiered match into one exact (R, N_AGG) float64 block: the additive
    slots sum and the trailing ``any_match`` slot takes the max — the
    host-side analogue of the in-launch psum/pmax combine (each partial
    is integer-valued and f32-exact, so the float64 sum is exact)."""
    out = np.zeros((n_programs, N_AGG), np.float64)
    for p in parts:
        p = np.asarray(p, np.float64)
        out[:, : N_AGG - 1] += p[:, : N_AGG - 1]
        np.maximum(out[:, N_AGG - 1], p[:, N_AGG - 1],
                   out=out[:, N_AGG - 1])
    return out


def _agg_dict(agg_np: np.ndarray, per_rule: Optional[np.ndarray] = None
              ) -> dict:
    out = {
        "count": float(agg_np[0]), "volume": float(agg_np[1]),
        "spc_used": float(agg_np[2]),
        "size_profile": agg_np[3:13].tolist(),
        "any_match": bool(agg_np[13] > 0.5),
    }
    if per_rule is not None and per_rule.shape[0] > 1:
        out["rule_count"] = per_rule[1:, 0].tolist()
        out["rule_volume"] = per_rule[1:, 1].tolist()
        out["rule_spc_used"] = per_rule[1:, 2].tolist()
    return out


def match_programs(arrays, exprs, strings, now: float,
                   use_kernel: Optional[bool] = None,
                   single_launch: Optional[bool] = None
                   ) -> Tuple[List[np.ndarray], dict, np.ndarray]:
    """Evaluate several core.policy Exprs over catalog columns at once.

    ``exprs[0]`` is the combined match criteria (its fused aggregates are
    returned); further exprs are per-rule conditions in priority order.
    Returns ``(masks, agg, rule_idx)``: one boolean mask per program, the
    aggregate dict of program 0 (plus ``rule_count``/``rule_volume``/
    ``rule_spc_used`` per-rule reductions when rules are present), and the
    (N,) int32 first-match-wins rule attribution (-1 = no rule).

    ``use_kernel=None`` selects the Pallas kernel on TPU and the jitted
    oracle everywhere else. ``single_launch`` (default True) evaluates the
    whole (R, P) program batch in ONE launch with attribution and per-rule
    reductions fused on-device; ``single_launch=False`` keeps the legacy
    one-launch-per-program path as a fallback and differential oracle.
    Raises PolicyError if any expr contains host-only (glob) predicates —
    callers fall back to the numpy mask path.
    """
    from ...core.policy import KERNEL_COLUMNS, compile_programs
    from ...core.telemetry import span as _tspan
    with _tspan("kernel.compile"):
        ops, colidx, operands = compile_programs(exprs, strings, now)
        kcols = column_stack(arrays)
    size_col = KERNEL_COLUMNS.index("size")
    blocks_col = KERNEL_COLUMNS.index("blocks")
    if use_kernel is None:
        use_kernel = _on_tpu()
    if single_launch is None:
        single_launch = True
    if single_launch:
        # the launch is async: the device wait lands in kernel.readback,
        # where the host actually blocks
        if use_kernel:
            m, rule, agg = policy_scan_batch(
                kcols, jnp.asarray(ops), jnp.asarray(colidx),
                jnp.asarray(operands), size_col=size_col,
                blocks_col=blocks_col, use_kernel=True)
        else:
            # off-TPU oracle: the unrolled static-program evaluator (same
            # outputs, ~an order of magnitude less memory traffic)
            ops_t, colidx_t = _program_tuples(ops, colidx)
            m, rule, agg = policy_scan_batch_unrolled(
                kcols, jnp.asarray(operands), ops_t=ops_t,
                colidx_t=colidx_t, size_col=size_col,
                blocks_col=blocks_col)
        with _tspan("kernel.readback"):
            m = np.asarray(m) > 0.5
            masks = [m[r] for r in range(m.shape[0])]
            per_rule = np.asarray(agg)
            rule = np.asarray(rule, dtype=np.int32)
        return masks, _agg_dict(per_rule[0], per_rule), rule
    # Fallback: one launch per program (program 0 still fuses mask +
    # aggregation in a single HBM pass; rule programs reuse the resident
    # column stack), attribution on the host.
    masks, aggs = [], []
    for r in range(ops.shape[0]):
        m, a = policy_scan(kcols, jnp.asarray(ops[r]),
                           jnp.asarray(colidx[r]),
                           jnp.asarray(operands[r]), size_col=size_col,
                           blocks_col=blocks_col, use_kernel=use_kernel)
        aggs.append(np.asarray(a))
        masks.append(np.asarray(m) > 0.5)
    per_rule = np.stack(aggs)
    return masks, _agg_dict(per_rule[0], per_rule), _attribute_np(masks)


def match_programs_mesh(store, exprs, now: float,
                        use_kernel: Optional[bool] = None):
    """Mesh-parallel sibling of :func:`match_programs`: evaluate the (R, P)
    program batch over a :class:`~repro.core.device_store.DeviceColumnStore`
    instead of a freshly uploaded column stack.

    The store refreshes stale shard groups by delta scatter (or full
    re-upload), launches :func:`mesh_policy_scan_batch` over the resident
    (D, n_cols, Rp) global array, and pulls back only the program-0 mask
    and the rule attribution. Returns a ``MeshMatch`` (see device_store):
    ``.plan(sort_by)`` yields the matched (fids, sizes, sort_keys,
    rule_idx) arrays and ``.agg`` the fused aggregate dict — same
    semantics as :func:`match_programs`, differential-tested equal.
    Raises PolicyError on host-only (glob) predicates.
    """
    return store.match(exprs, now, use_kernel=use_kernel)


def scan_catalog(catalog, expr, now: float, use_kernel: bool = True,
                 store=None) -> Tuple[np.ndarray, dict]:
    """Run a core.policy expression over a Catalog via the kernel path.

    Only numeric/categorical predicates compile to the kernel program;
    glob predicates raise PolicyError (callers fall back to Expr.mask).
    Returns (matching fids, aggregate dict). When ``store`` (a
    :class:`~repro.core.device_store.DeviceColumnStore` over the same
    catalog) is given, the scan runs mesh-parallel over the device-resident
    column stacks — no host-side concat, no host→device re-upload.
    """
    if store is not None:
        if store.catalog is not catalog:
            from ...core.policy import PolicyError
            raise PolicyError("device store wraps a different catalog "
                              "than the one passed to scan_catalog")
        match = store.match([expr], now, use_kernel=use_kernel)
        fids, _sizes, _sort, _ridx = match.plan("size")
        return fids, match.agg
    from ...core.policy import KERNEL_COLUMNS, compile_program
    from ...core.telemetry import span as _tspan
    with _tspan("kernel.compile"):
        arrays = catalog.arrays()
        ops, colidx, operands = compile_program(expr, catalog.strings, now)
        cols = jnp.stack([jnp.asarray(arrays[c], jnp.float32)
                          for c in KERNEL_COLUMNS], axis=0)
    size_col = KERNEL_COLUMNS.index("size")
    blocks_col = KERNEL_COLUMNS.index("blocks")
    mask, agg = policy_scan(cols, jnp.asarray(ops), jnp.asarray(colidx),
                            jnp.asarray(operands), size_col=size_col,
                            blocks_col=blocks_col, use_kernel=use_kernel)
    with _tspan("kernel.readback"):
        mask_np = np.asarray(mask) > 0.5
        agg_np = np.asarray(agg)
    return arrays["fid"][mask_np], {
        "count": float(agg_np[0]), "volume": float(agg_np[1]),
        "spc_used": float(agg_np[2]),
        "size_profile": agg_np[3:13].tolist(),
        "any_match": bool(agg_np[13] > 0.5),
    }
