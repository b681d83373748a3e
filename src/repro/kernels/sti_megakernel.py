"""Fully fused Pallas valuation megakernel: distance -> streaming sort ->
method update in ONE `pallas_call` per streaming step.

The three-stage step (`sti_pipeline._stream_body`) round-trips the (tb, n)
distance block through HBM twice: once out of the distance matmul and once
into the sort/fill stages. This kernel keeps each distance TILE in VMEM
until it has been folded into a running sorted stream and the method's
accumulator, flash-attention-style (`kernels/flash_attention.py` is the
in-repo pattern): per (block_t, block_n) tile it

  1. computes the squared-distance block from `(x_test_tile, x_train_tile)`
     -- optionally in bf16 with an f32 accumulator
     (`preferred_element_type=jnp.float32`; see "Mixed precision" below);
  2. merges the tile into a running (distance, index, label-match) triple
     sorted by (d2, index) -- `merge_sorted_tile` below, a two-key
     `jax.lax.sort` whose index tie-break makes the final order
     bit-identical to `jnp.argsort(d2, stable=True)` and therefore the
     ranks bit-identical to `ranks_from_order`;
  3. builds the method's SORTED-coordinate tables (g/u for sti/sii,
     per-point values for knn_shapley/wknn/loo -- the
     `stream_kernels.make_megakernel_tables` closures) in VMEM scratch, and
  4. scatters them into the ALIASED accumulator tiles
     (`input_output_aliases`), reusing `sti_fill._tile_sum` and the rect
     row-index-base convention (`row_offset`) so the sharded (n/D, n)
     row-block case runs the very same kernel.

The running stream is kept at width n (the full sorted order), not a small
top-k: every exact recurrence this repo streams (`superdiagonal_g`,
`knn_shapley_from_sorted`, the LOO window) consumes ALL n sorted positions.
`merge_sorted_tile` itself is width-generic -- the property tests exercise
it as a streaming top-k against `jax.lax.top_k` -- but the pipeline
instantiates it at the exact width. See DESIGN.md Sec. 17 for the grid /
VMEM layout diagram and the sharded collective-bytes argument.

Mixed precision: `compute_dtype="bfloat16"` casts ONLY the distance-matmul
operands; the cross-term accumulates in f32 (`preferred_element_type`) and
the row/column norms, the sort keys, and every method table stay f32. Only
the RANKING can therefore differ from the f32 path (and `wknn`'s distance
weights); on rank agreement every unweighted method is bit-identical.

Interpret-mode fallback: like every Pallas kernel in this repo the wrapper
defaults to `interpret=True` off-TPU, so CPU CI runs the same kernel body
as ordinary JAX ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sti_fill import _tile_sum

__all__ = [
    "merge_sorted_tile",
    "streaming_merge_reference",
    "sti_megakernel",
    "point_megakernel",
    "MEGAKERNEL_FILL",
    "MEGAKERNEL_PARAMS",
    "megakernel_static",
]

# the registry/CLI name that routes a streaming step to this module
MEGAKERNEL_FILL = "megakernel"

# the static knobs the step builders accept as fill_params
MEGAKERNEL_PARAMS = frozenset((
    "block_t", "block_n", "block_rows", "block_cols", "compute_dtype",
    "interpret",
))


def megakernel_static(fill_params) -> tuple:
    """Filter a fill_params dict down to the megakernel's static knobs and
    return them as the hashable sorted tuple the cached step builders key
    on (unknown keys -- e.g. a square-fill `chunk` leaking through an
    `auto` resolution -- are dropped, matching `_accepted_params`)."""
    params = {
        key: value for key, value in dict(fill_params or {}).items()
        if key in MEGAKERNEL_PARAMS
    }
    return tuple(sorted(params.items()))

# sentinel distance for padded train columns: +inf sorts after every real
# entry (including the service's soft-deleted ~1e30 sentinel distances)
_PAD_D2 = float("inf")


def merge_sorted_tile(d2_run, idx_run, match_run, d2_tile, idx_tile,
                      match_tile):
    """One online merge step of the streaming sort.

    Args:
      d2_run/idx_run/match_run: (..., w) running triple, sorted by
        (d2, index) ascending; `w` is the kept width.
      d2_tile/idx_tile/match_tile: (..., bn) one train tile's distances,
        GLOBAL column indices, and 0/1 label matches (any order).

    Returns the merged running triple, again width `w`: the w smallest
    entries of the union under the lexicographic (d2, index) key. The
    two-key `jax.lax.sort` breaks distance ties by the smaller global
    index -- exactly the tie-break of `jnp.argsort(d2, stable=True)` -- so
    streaming the full width over all tiles reproduces the stable argsort
    (and `ranks_from_order` of it) bit-for-bit.
    """
    keep = d2_run.shape[-1]
    d2 = jnp.concatenate([d2_run, d2_tile], axis=-1)
    idx = jnp.concatenate([idx_run, idx_tile], axis=-1)
    match = jnp.concatenate([match_run, match_tile], axis=-1)
    d2, idx, match = jax.lax.sort((d2, idx, match), dimension=-1, num_keys=2)
    return d2[..., :keep], idx[..., :keep], match[..., :keep]


def streaming_merge_reference(d2, match, *, n_keep=None, block_n=128):
    """Drive `merge_sorted_tile` over precomputed (t, n) distances in plain
    jnp (no Pallas): the oracle surface the property tests compare against
    `jax.lax.top_k` / `ranks_from_order`. Returns the (t, n_keep) sorted
    (d2, index, match) triple; `n_keep=None` keeps the full width n."""
    t, n = d2.shape
    keep = n if n_keep is None else int(n_keep)
    run = (
        jnp.full((t, keep), _PAD_D2, jnp.float32),
        jnp.full((t, keep), n, jnp.int32),
        jnp.zeros((t, keep), jnp.float32),
    )
    for start in range(0, n, max(1, int(block_n))):
        end = min(n, start + max(1, int(block_n)))
        cols = jnp.arange(start, end, dtype=jnp.int32)
        run = merge_sorted_tile(
            *run,
            d2[:, start:end].astype(jnp.float32),
            jnp.broadcast_to(cols, (t, end - start)),
            match[:, start:end].astype(jnp.float32),
        )
    return run


def _ranks_of(order):
    """Invert a (t, n) sorted-order permutation into integer ranks: the
    in-kernel twin of `core.sti_knn.ranks_from_order` (same scatter)."""
    t, n = order.shape
    ranks = jnp.zeros_like(order)
    return ranks.at[jnp.arange(t)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(n, dtype=order.dtype), order.shape)
    )


def _gather_sum(r_rows, vals):
    """sum_p vals[p, r_rows[p, :]] -> (BR,): the vector twin of
    `sti_fill._tile_sum`, used for the diag / point-value scatter (vals is
    a sorted-coordinate table, r_rows a rank window in train coordinates)."""
    tt = r_rows.shape[0]

    def body(p, acc):
        return acc + jnp.take(vals[p], r_rows[p], axis=0)

    return jax.lax.fori_loop(
        0, tt, body, jnp.zeros((r_rows.shape[1],), jnp.float32)
    )


def _stream_sorted(xb_ref, yb_ref, xtr_ref, ytr_ref, *, n, block_n,
                   n_train_pad, compute_dtype):
    """The shared rank phase of both kernels: stream the train tiles through
    the distance + online-merge loop and return the (tt, n) sorted
    (d2, index, match) triple plus the (tt,) test labels' validity-free
    data. Runs entirely on VMEM-resident refs; the (tt, n_train_pad)
    distance block is never materialized."""
    xb = xb_ref[...].astype(jnp.float32)              # (tt, d)
    yb = yb_ref[...][:, 0]                            # (tt,) int
    cdtype = jnp.dtype(compute_dtype)
    # f32 compute is f32-exact; bf16 is the explicit mixed-precision opt-in
    precision = (jax.lax.Precision.HIGHEST if cdtype == jnp.float32
                 else None)
    xq = xb.astype(cdtype)
    xb2 = jnp.sum(xb * xb, axis=-1, keepdims=True)    # (tt, 1) f32
    tt = xb.shape[0]
    run = (
        jnp.full((tt, n), _PAD_D2, jnp.float32),
        jnp.full((tt, n), n_train_pad, jnp.int32),
        jnp.zeros((tt, n), jnp.float32),
    )

    def fold(j, run):
        start = j * block_n
        xt = xtr_ref[pl.ds(start, block_n), :].astype(jnp.float32)
        yt = ytr_ref[pl.ds(start, block_n), :][:, 0]
        cross = jax.lax.dot_general(
            xq, xt.astype(cdtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )                                              # (tt, bn) f32 accum
        xt2 = jnp.sum(xt * xt, axis=-1)                # (bn,) f32
        d2 = jnp.maximum(xb2 - 2.0 * cross + xt2[None, :], 0.0)
        col = start + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
        d2 = jnp.where(col < n, d2, _PAD_D2)           # padded cols sort last
        match = (yt[None, :] == yb[:, None]).astype(jnp.float32)
        match = jnp.broadcast_to(match, d2.shape)
        return merge_sorted_tile(*run, d2, col, match)

    return jax.lax.fori_loop(0, n_train_pad // block_n, fold, run)


def _scratch_width(n, nr, block_rows, block_cols):
    """Width of the per-t-tile VMEM tables (ranks / g / u / values).

    Row windows of the aliased accumulator address GLOBAL train rows
    [row_offset + ia*block_rows, ... + block_rows); with `row_offset` up to
    n - nr (the sharded last row block) and the row extent padded to a
    block multiple, windows can reach past n -- as can padded column
    blocks. Those positions hold the sentinel rank n over zero-padded
    tables, so padded accumulator rows/cols gather exact zeros."""
    pad_r = (-nr) % block_rows
    pad_c = (-n) % block_cols
    return max(n + pad_c, (n - nr) + nr + pad_r)


def _pack_tables(ranks, tables, n_s):
    """Pad the (tt, n) rank/value tables to the scratch width: ranks pad
    with the sentinel rank n, value tables with exact zeros (so sentinel
    gathers contribute nothing to padded accumulator rows/cols)."""
    tt, n = ranks.shape
    if n_s == n:
        return ranks, tables
    r_pad = jnp.full((tt, n_s - n), n, ranks.dtype)
    ranks = jnp.concatenate([ranks, r_pad], axis=-1)
    tables = tuple(
        jnp.concatenate([tab, jnp.zeros((tt, n_s - n), tab.dtype)], axis=-1)
        for tab in tables
    )
    return ranks, tables


def _interaction_kernel(row_off_ref, xb_ref, yb_ref, mask_ref, xtr_ref,
                        ytr_ref, acc_in_ref, diag_in_ref, acc_ref, diag_ref,
                        g_s, u_s, r_s, *, tables, n, n_s, block_n,
                        block_rows, block_cols, n_train_pad, compute_dtype):
    """Grid (t_tiles, row_blocks, col_blocks), test dim outermost. The rank
    phase runs once per t-tile (first row/col visit) and parks the sorted
    tables in VMEM scratch; every visit then read-modify-writes its aliased
    (block_rows, block_cols) accumulator tile, exactly the revisiting
    discipline of `sti_fill._acc_kernel`."""
    tt_i = pl.program_id(0)
    ia = pl.program_id(1)
    jb = pl.program_id(2)

    @pl.when(jnp.logical_and(ia == 0, jb == 0))
    def _rank_phase():
        d2s, order, match_s = _stream_sorted(
            xb_ref, yb_ref, xtr_ref, ytr_ref, n=n, block_n=block_n,
            n_train_pad=n_train_pad, compute_dtype=compute_dtype,
        )
        mask = mask_ref[...][:, 0]
        g, u = tables(d2s, match_s, mask)
        ranks, (g, u) = _pack_tables(_ranks_of(order), (g, u), n_s)
        g_s[...] = g
        u_s[...] = u
        r_s[...] = ranks

    # seed the aliased tiles from the incoming accumulator on first visit
    @pl.when(tt_i == 0)
    def _seed_acc():
        acc_ref[...] = acc_in_ref[...]

    @pl.when(jnp.logical_and(tt_i == 0, jb == 0))
    def _seed_diag():
        diag_ref[...] = diag_in_ref[...]

    row_base = row_off_ref[0, 0] + ia * block_rows
    ra = r_s[:, pl.ds(row_base, block_rows)]           # (tt, BR)
    rb = r_s[:, pl.ds(jb * block_cols, block_cols)]    # (tt, BC)
    acc_ref[...] += _tile_sum(ra, rb, g_s[...])

    @pl.when(jb == 0)
    def _diag():
        diag_ref[...] += _gather_sum(ra, u_s[...])[:, None]


def _point_kernel(row_off_ref, xb_ref, yb_ref, mask_ref, xtr_ref, ytr_ref,
                  vec_in_ref, vec_ref, v_s, r_s, *, tables, n, n_s, block_n,
                  block_rows, n_train_pad, compute_dtype):
    """Point-method twin: grid (t_tiles, row_blocks); the sorted-coordinate
    per-point value table replaces g/u, and the aliased (block_rows, 1)
    vector tile accumulates its rank-gathered row window."""
    tt_i = pl.program_id(0)
    ia = pl.program_id(1)

    @pl.when(ia == 0)
    def _rank_phase():
        d2s, order, match_s = _stream_sorted(
            xb_ref, yb_ref, xtr_ref, ytr_ref, n=n, block_n=block_n,
            n_train_pad=n_train_pad, compute_dtype=compute_dtype,
        )
        mask = mask_ref[...][:, 0]
        vals = tables(d2s, match_s, mask)
        ranks, (vals,) = _pack_tables(_ranks_of(order), (vals,), n_s)
        v_s[...] = vals
        r_s[...] = ranks

    @pl.when(tt_i == 0)
    def _seed():
        vec_ref[...] = vec_in_ref[...]

    row_base = row_off_ref[0, 0] + ia * block_rows
    ra = r_s[:, pl.ds(row_base, block_rows)]
    vec_ref[...] += _gather_sum(ra, v_s[...])[:, None]


def _vmem(shape, dtype):
    """VMEM scratch allocation (TPU backend import deferred, like
    `flash_attention._vmem`)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _resolve_blocks(tb, n, nr, block_t, block_n, block_rows, block_cols,
                    interpret):
    """Resolve the tile shapes. Defaults keep the three (bt, n_s) scratch
    tables under ~4 MiB of VMEM apiece (the `sti_fill` budget) and -- in
    interpret mode, where every grid step replays the body as Python-driven
    JAX ops -- prefer the coarsest legal grid."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_t is None:
        block_t = max(1, min(tb, (4 << 20) // max(4 * n, 1)))
    if block_n is None:
        block_n = min(n, 512)
    if block_rows is None:
        block_rows = min(nr, n if interpret else 256)
    if block_cols is None:
        block_cols = min(n, n if interpret else 256)
    bt = max(1, min(int(block_t), tb))
    bn = max(1, min(int(block_n), n))
    br = max(1, min(int(block_rows), nr))
    bc = max(1, min(int(block_cols), n))
    return bt, bn, br, bc, interpret


def _pad_operands(xb, yb, mask, x_train, y_train, row_offset, bt, bn):
    """Pad the batch/train operands to block multiples and shape the 1-D
    operands (labels, mask, row offset) as the 2-D blocks Pallas TPU wants.
    Padded test rows carry mask 0 (zero contribution); padded train columns
    are masked to +inf distance inside the kernel (`col < n`)."""
    tb, d = xb.shape
    n = x_train.shape[0]
    pad_t = (-tb) % bt
    pad_n = (-n) % bn
    xb_p = jnp.pad(xb.astype(jnp.float32), ((0, pad_t), (0, 0)))
    yb_p = jnp.pad(yb.astype(jnp.int32), ((0, pad_t),))[:, None]
    mask_p = jnp.pad(mask.astype(jnp.float32), ((0, pad_t),))[:, None]
    xtr_p = jnp.pad(x_train.astype(jnp.float32), ((0, pad_n), (0, 0)))
    ytr_p = jnp.pad(y_train.astype(jnp.int32), ((0, pad_n),))[:, None]
    if row_offset is None:
        row_off = jnp.zeros((1, 1), jnp.int32)
    else:
        row_off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    return xb_p, yb_p, mask_p, xtr_p, ytr_p, row_off, n + pad_n


def sti_megakernel(acc, diag, xb, yb, mask, x_train, y_train, *, k, mode="sti",
                   row_offset=None, block_t=None, block_n=None,
                   block_rows=None, block_cols=None,
                   compute_dtype="float32", interpret=None):
    """One fused interaction streaming step in a single `pallas_call`:

        (acc, diag, xb, yb, mask, x_train, y_train) -> (acc, diag)

    acc is the (nr, n) accumulator row block -- the full square when
    `row_offset is None`, a sharded (n/D, n) block when `row_offset` is the
    device's global row base (may be traced, e.g.
    `jax.lax.axis_index(axis) * nl` inside a shard_map body) -- and diag its
    (nr,) diagonal rows. Semantics match the three-stage fused step
    bit-for-bit in ranks and to ~1e-5 in values (the fill's tile summation
    order differs); `compute_dtype="bfloat16"` opts into the mixed-precision
    distance matmul (module docstring)."""
    nr, n = acc.shape
    tb = xb.shape[0]
    bt, bn, br, bc, interpret = _resolve_blocks(
        tb, n, nr, block_t, block_n, block_rows, block_cols, interpret
    )
    xb_p, yb_p, mask_p, xtr_p, ytr_p, row_off, n_train_pad = _pad_operands(
        xb, yb, mask, x_train, y_train, row_offset, bt, bn
    )
    d = xb_p.shape[1]
    pad_r = (-nr) % br
    pad_c = (-n) % bc
    acc_p = jnp.pad(acc, ((0, pad_r), (0, pad_c)))
    diag_p = jnp.pad(diag, ((0, pad_r),))[:, None]
    n_s = _scratch_width(n, nr, br, bc)
    tables = _sorted_tables(mode, int(k), None)
    grid = (xb_p.shape[0] // bt, acc_p.shape[0] // br, acc_p.shape[1] // bc)
    kernel = functools.partial(
        _interaction_kernel, tables=tables, n=n, n_s=n_s, block_n=bn,
        block_rows=br, block_cols=bc, n_train_pad=n_train_pad,
        compute_dtype=compute_dtype,
    )
    acc_out, diag_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda tt, ia, jb: (0, 0)),        # row off
            pl.BlockSpec((bt, d), lambda tt, ia, jb: (tt, 0)),      # xb
            pl.BlockSpec((bt, 1), lambda tt, ia, jb: (tt, 0)),      # yb
            pl.BlockSpec((bt, 1), lambda tt, ia, jb: (tt, 0)),      # mask
            pl.BlockSpec(xtr_p.shape, lambda tt, ia, jb: (0, 0)),   # x_train
            pl.BlockSpec(ytr_p.shape, lambda tt, ia, jb: (0, 0)),   # y_train
            pl.BlockSpec((br, bc), lambda tt, ia, jb: (ia, jb)),    # acc in
            pl.BlockSpec((br, 1), lambda tt, ia, jb: (ia, 0)),      # diag in
        ],
        out_specs=[
            pl.BlockSpec((br, bc), lambda tt, ia, jb: (ia, jb)),
            pl.BlockSpec((br, 1), lambda tt, ia, jb: (ia, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(acc_p.shape, jnp.float32),
            jax.ShapeDtypeStruct(diag_p.shape, jnp.float32),
        ],
        scratch_shapes=[
            _vmem((bt, n_s), jnp.float32),   # g (sorted)
            _vmem((bt, n_s), jnp.float32),   # u (sorted)
            _vmem((bt, n_s), jnp.int32),     # ranks (train coords)
        ],
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
    )(row_off, xb_p, yb_p, mask_p, xtr_p, ytr_p, acc_p, diag_p)
    return acc_out[:nr, :n], diag_out[:nr, 0]


def point_megakernel(vec, xb, yb, mask, x_train, y_train, *, method, k,
                     opts=None, row_offset=None, block_t=None, block_n=None,
                     block_rows=None, compute_dtype="float32",
                     interpret=None):
    """One fused point-value streaming step in a single `pallas_call`:

        (vec, xb, yb, mask, x_train, y_train) -> vec

    vec is the (nr,) accumulator row block (full n single-device, n/D rows
    sharded -- `row_offset` exactly as in `sti_megakernel`). `method` is any
    registered point method ("knn_shapley" / "wknn" / "loo"); `opts` carries
    its statics (e.g. the wknn weight kind)."""
    nr = vec.shape[0]
    n = x_train.shape[0]
    tb = xb.shape[0]
    bt, bn, br, _, interpret = _resolve_blocks(
        tb, n, nr, block_t, block_n, block_rows, None, interpret
    )
    xb_p, yb_p, mask_p, xtr_p, ytr_p, row_off, n_train_pad = _pad_operands(
        xb, yb, mask, x_train, y_train, row_offset, bt, bn
    )
    d = xb_p.shape[1]
    pad_r = (-nr) % br
    vec_p = jnp.pad(vec, ((0, pad_r),))[:, None]
    n_s = _scratch_width(n, nr, br, max(1, n))
    tables = _sorted_tables(method, int(k), opts)
    grid = (xb_p.shape[0] // bt, vec_p.shape[0] // br)
    kernel = functools.partial(
        _point_kernel, tables=tables, n=n, n_s=n_s, block_n=bn,
        block_rows=br, n_train_pad=n_train_pad, compute_dtype=compute_dtype,
    )
    vec_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda tt, ia: (0, 0)),        # row offset
            pl.BlockSpec((bt, d), lambda tt, ia: (tt, 0)),      # xb
            pl.BlockSpec((bt, 1), lambda tt, ia: (tt, 0)),      # yb
            pl.BlockSpec((bt, 1), lambda tt, ia: (tt, 0)),      # mask
            pl.BlockSpec(xtr_p.shape, lambda tt, ia: (0, 0)),   # x_train
            pl.BlockSpec(ytr_p.shape, lambda tt, ia: (0, 0)),   # y_train
            pl.BlockSpec((br, 1), lambda tt, ia: (ia, 0)),      # vec in
        ],
        out_specs=pl.BlockSpec((br, 1), lambda tt, ia: (ia, 0)),
        out_shape=jax.ShapeDtypeStruct(vec_p.shape, jnp.float32),
        scratch_shapes=[
            _vmem((bt, n_s), jnp.float32),   # values (sorted)
            _vmem((bt, n_s), jnp.int32),     # ranks (train coords)
        ],
        input_output_aliases={6: 0},
        interpret=interpret,
    )(row_off, xb_p, yb_p, mask_p, xtr_p, ytr_p, vec_p)
    return vec_out[:nr, 0]


def _sorted_tables(method, k, opts):
    """Resolve the method's sorted-coordinate table closure (registered in
    `stream_kernels`); split out so both kernels share the import seam."""
    from repro.kernels.stream_kernels import make_megakernel_tables

    return make_megakernel_tables(method, k, opts=opts)
