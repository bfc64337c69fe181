"""jit'd wrapper: layout preparation + kernel dispatch.

The layout step (sort by destination, pad so edge blocks never straddle
output tiles) runs in XLA; the scatter-reduction runs in the Pallas kernel
on the MXU. On non-TPU backends `interpret=True` executes the same kernel
body for correctness tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce.kernel import (DEFAULT_BLOCK_E,
                                                 DEFAULT_BLOCK_R,
                                                 DEFAULT_BLOCK_V,
                                                 mean_rows_kernel,
                                                 segment_sum_kernel)


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("n_segments", "block_e", "block_v",
                                   "interpret", "trim"))
def segment_sum_sorted(msgs, seg_ids, n_segments: int,
                       block_e: int = DEFAULT_BLOCK_E,
                       block_v: int = DEFAULT_BLOCK_V,
                       interpret: bool | None = None,
                       trim: bool = True):
    """Segment-sum of msgs [E, d] by seg_ids [E] (MUST be sorted ascending;
    id >= n_segments = padding). Returns [n_segments, d].

    trim=False is the opt-out for block-aligned callers that want the raw
    padded [n_segments_pad, d] kernel output (n_segments_pad = n_segments
    rounded up to block_v; the tail rows are zero). It used to be the only
    behaviour, which silently handed every caller an off-by-block tail to
    slice — now the slice happens here.
    """
    if interpret is None:
        interpret = not _is_tpu()
    E, d = msgs.shape
    n_vblk = -(-n_segments // block_v)

    # ---- layout: pad edges so no block spans two output tiles ----------
    vblk_of_edge = jnp.minimum(seg_ids // block_v, n_vblk - 1)
    # within-block capacity: each destination tile's edges padded up to a
    # multiple of block_e by routing them to per-tile padded ranges.
    counts = jnp.zeros((n_vblk,), jnp.int32).at[vblk_of_edge].add(
        jnp.where(seg_ids < n_segments, 1, 0))
    padded_counts = ((counts + block_e - 1) // block_e) * block_e
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(padded_counts)[:-1]])
    # rank of each edge within its tile (seg_ids sorted => stable arange)
    tile_start_edge = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(E, dtype=jnp.int32) - tile_start_edge[vblk_of_edge]
    pos = starts[vblk_of_edge] + rank
    e_cap = E + n_vblk * block_e          # worst-case padded length
    e_cap = ((e_cap + block_e - 1) // block_e) * block_e
    valid = seg_ids < n_segments
    pos = jnp.where(valid, pos, e_cap - 1)  # dump padding at the very end

    msgs_p = jnp.zeros((e_cap, d), msgs.dtype).at[pos].add(
        jnp.where(valid[:, None], msgs, 0.0))
    seg_local = jnp.full((e_cap,), block_v, jnp.int32).at[pos].set(
        jnp.where(valid, seg_ids % block_v, block_v))
    # which output tile each edge block belongs to
    n_eblk = e_cap // block_e
    eblk_starts = jnp.arange(n_eblk, dtype=jnp.int32) * block_e
    cum = jnp.cumsum(padded_counts)
    eblk_to_vblk = jnp.searchsorted(cum, eblk_starts, side="right"
                                    ).astype(jnp.int32)
    eblk_to_vblk = jnp.minimum(eblk_to_vblk, n_vblk - 1)
    first = jnp.concatenate([jnp.ones(1, jnp.int32),
                             (eblk_to_vblk[1:] != eblk_to_vblk[:-1])
                             .astype(jnp.int32)])
    # tiles with zero edges are never visited: fold an explicit zero of
    # those tiles into the result afterwards.
    out = segment_sum_kernel(msgs_p, seg_local, eblk_to_vblk, first,
                             n_vblocks=n_vblk, block_e=block_e,
                             block_v=block_v, interpret=interpret)
    visited = jnp.zeros((n_vblk,), bool).at[eblk_to_vblk].set(True)
    out = out.reshape(n_vblk, block_v, d)
    out = jnp.where(visited[:, None, None], out, 0.0)
    out = out.reshape(n_vblk * block_v, d)
    return out[:n_segments] if trim else out


def gather_segment_sum(x, senders, receivers, n_nodes: int, edge_mask=None,
                       block_e: int = DEFAULT_BLOCK_E,
                       block_v: int = DEFAULT_BLOCK_V,
                       interpret: bool | None = None):
    """Fused-graph entry point: sorts edges by destination, gathers source
    rows, reduces with the Pallas kernel. Drop-in for
    graph.segment.segment_sum(x[senders], receivers, n_nodes, mask)."""
    E = senders.shape[0]
    seg = jnp.where(edge_mask, receivers, n_nodes) if edge_mask is not None \
        else receivers
    order = jnp.argsort(seg)
    msgs = x[senders[order]]
    return segment_sum_sorted(msgs, seg[order], n_nodes, block_e=block_e,
                              block_v=block_v, interpret=interpret)


# ==================== streaming-tick delivery variants (ISSUE 3 tentpole)

@partial(jax.jit, static_argnames=("n_rows", "mode", "block_e", "block_v",
                                   "interpret"))
def segment_deliver(idx, vec, cnt, n_rows: int, mode: str = "add",
                    block_e: int = DEFAULT_BLOCK_E,
                    block_v: int = DEFAULT_BLOCK_V,
                    interpret: bool | None = None):
    """Fixed-capacity message delivery as ONE sorted segment reduction.

    idx [C] int32 destination rows — rows outside [0, n_rows) are the
    drop sentinel (invalid/padding records, `state.local_index` style);
    vec [C, d] float payload; cnt [C] float scalar count deltas.

    Returns (vec_out [n_rows, d], cnt_out [n_rows], touched [n_rows]):
      mode="add" : per-row sums of vec and cnt (aggregator RMI apply);
      mode="set" : the LAST valid writer's vec/cnt per row (feature
                   delivery; matches XLA scatter-set update order).
    touched[r] is True iff any valid record addressed row r — the
    changed/dirty flag the tick needs, accumulated in the same kernel
    pass (the count column of the packed payload).

    Layout plane (XLA): mask + stable sort by destination, pack
    [vec | cnt | touch] into one [C, d+2] payload. Compute plane
    (Pallas): one `segment_sum_kernel` pass over the packed payload.
    """
    if interpret is None:
        interpret = not _is_tpu()
    C, d = vec.shape
    idx = idx.astype(jnp.int32)
    valid = (idx >= 0) & (idx < n_rows)
    seg = jnp.where(valid, idx, n_rows)
    order = jnp.argsort(seg, stable=True)     # stable: record order per row
    seg_s = seg[order]
    vec_s, cnt_s, val_s = vec[order], cnt[order], valid[order]
    if mode == "set":
        # last-writer-wins: only the final record of each destination run
        # carries payload into the sum (stable sort preserves write order)
        is_last = jnp.concatenate([seg_s[1:] != seg_s[:-1],
                                   jnp.ones((1,), bool)])
        live = val_s & is_last
    elif mode == "add":
        live = val_s
    else:
        raise ValueError(f"segment_deliver mode must be 'add' or 'set', "
                         f"got {mode!r}")
    payload = jnp.concatenate(
        [jnp.where(live[:, None], vec_s, 0.0),
         jnp.where(live, cnt_s, 0.0)[:, None],
         live.astype(vec.dtype)[:, None]], axis=1)
    out = segment_sum_sorted(payload, seg_s, n_rows, block_e=block_e,
                             block_v=block_v, interpret=interpret)
    return out[:, :d], out[:, d], out[:, d + 1] > 0


@partial(jax.jit, static_argnames=("block_r", "interpret"))
def mean_rows(sums, cnts, block_r: int = DEFAULT_BLOCK_R,
              interpret: bool | None = None):
    """Aggregator read at selected rows: sums/cnts with cnt <= 0 rows
    reading ZERO (empty-neighborhood contract of aggregators.mean_read —
    a remove-emptied row must not read its stale sigma residual).

    Pads K up to a block_r multiple (padding counts are 1 so the padded
    rows divide cleanly) and runs the VPU `mean_rows_kernel`."""
    if interpret is None:
        interpret = not _is_tpu()
    K, d = sums.shape
    k_pad = max(block_r, -(-K // block_r) * block_r)
    sums_p = jnp.zeros((k_pad, d), sums.dtype).at[:K].set(sums)
    cnts_p = jnp.ones((k_pad, 1), sums.dtype).at[:K, 0].set(cnts)
    out = mean_rows_kernel(sums_p, cnts_p, block_r=block_r,
                           interpret=interpret)
    return out[:K]


@partial(jax.jit, static_argnames=("block_e", "block_v", "block_r",
                                   "interpret"))
def rmi_apply_read(agg, cnt, idx, vec, dcnt, read_idx,
                   block_e: int = DEFAULT_BLOCK_E,
                   block_v: int = DEFAULT_BLOCK_V,
                   block_r: int = DEFAULT_BLOCK_R,
                   interpret: bool | None = None):
    """Fused RMI-apply + mean read in ONE call (paper §4.2.1 primitive).

    Applies a tick's aggregator RMI records (idx, vec, dcnt) onto the
    (agg [R, d], cnt [R]) synopsis with one `segment_deliver` pass, then
    reads the MEAN synopsis at `read_idx` [K] through `mean_rows` — the
    full [R, d] mean table is never materialized, only the K picked rows.

    The streaming tick itself calls the two halves separately
    (PallasDelivery.deliver_add in deliver_rmis, .agg_read_rows in
    forward_psi) because the read rows are only chosen AFTER the dirty
    flags exist; this single-call form is for callers that know their
    read rows up front, and is the tested contract
    (`rmi_apply_read_ref`) both halves are pinned to.

    Returns (agg', cnt', dirty [R] bool, reads [K, d]).
    """
    d_vec, d_cnt, dirty = segment_deliver(
        idx, vec, dcnt, agg.shape[0], mode="add", block_e=block_e,
        block_v=block_v, interpret=interpret)
    agg2, cnt2 = agg + d_vec, cnt + d_cnt
    reads = mean_rows(agg2[read_idx], cnt2[read_idx], block_r=block_r,
                      interpret=interpret)
    return agg2, cnt2, dirty, reads
