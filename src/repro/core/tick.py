"""The per-layer micro-tick: streaming (Alg. 1) and windowed (Alg. 2)
forward pass, factored into SIX planes — a part-local COMPUTE plane
(the four stages below, ISSUE 2), an explicit ROUTING plane
(`dist/router.py`), a pluggable DELIVERY plane (`core/delivery.py`,
ISSUE 3) that lands routed records in the local state blocks, a
QUERY plane (`serve/query.py`, ISSUE 4) that answers point queries from
the state the other three maintain — it runs after the layer ticks and
the sink update (see `core/pipeline.py`), reading this module's
red/fwd pending flags as the per-target freshness signal — a
TRAINING plane (`core/train_plane.py`, ISSUE 8) that closes the tick
with a windowed online training step backpropagating through the live
caches the compute plane just refreshed — and a TELEMETRY plane
(`repro/telemetry/`, ISSUE 9) that WATCHES the other five:
`PipelineConfig.telemetry=True` lights up exact per-plane occupancy
counters in TickStats (defer-ring gauges, peak route-bucket demand)
plus a per-tick occupancy row riding the super-tick scan, streamed to
an on-disk trace the capacity advisor replays. The default
(telemetry=False) emits static zeros — the program is bit-for-bit the
five-plane tick.

One tick = two routing rounds (DESIGN §2), four pure stages with a
Router delivery between them:

  round_a_apply : master-addressed feature updates land at local masters
                  (delivery.deliver_set); selectiveBroadcast records for
                  changed masters are EMITTED as a part-addressed
                  `MsgBatch` (not scattered into other parts' rows).
       -- router.route_lanes((bcast,), ...) --
  round_b_emit  : delivered broadcasts apply at local replicas
                  (delivery.deliver_set); per-vertex feature *deltas* and
                  new-edge messages become aggregator RMI records
                  (delta, dcnt) addressed to destination masters.
                  reduce / replace / remove all collapse to additive
                  records (core/aggregators.py).
       -- router.route_lanes((rmis, [query wire]), ...) --
                  each route_lanes call is ONE packed all_to_all (ISSUE 5)
                  with per-destination buckets capped by route_cap;
                  overflow defers into per-lane rings in LayerState
                  (bc_defer/rmi_defer) and re-enters next tick.
  deliver_rmis  : ONE delivery (delivery.deliver_add) applies any RMI mix
                  at the local masters — a flat scatter-add on the "xla"
                  backend, a sorted Pallas segment reduction on "pallas" —
                  over the delivered lane's live rows only, in steps of
                  `deliver_chunk` rows.
  forward_psi   : dirty masters run the update (psi) under the intra-layer
                  window and emit into a per-part capacity-limited outbox;
                  the aggregator read goes through delivery.agg_read_rows
                  (fused on "pallas": only the picked rows are divided).

Every stage sees only its LOCAL block of parts ([P_loc, ...], global part
ids offset by `part0`), so the identical body runs on one device
(LocalRouter: part0=0, P_loc=P) and inside a `shard_map` over the mesh
(MeshRouter: part0 = axis_index * P_loc) — on either delivery backend.
Scalar TickStats are reduced through `router.psum`; the per-part `busy`
vector stays local and is concatenated by the shard_map out-spec.

Stage placement (hybrid parallelism, ISSUE 7): on a 2-D ("stage",
"data") mesh this same body also runs unmodified per PIPELINE STAGE —
the L layers are placed round-robin on the stage axis (layer l = round
r * S + s lives on stage s) and `core/pipeline.py:_tick_program_2d`
calls `layer_tick_body` once per ROUND with that stage's slice of the
stacked layer state. The inbox then comes from the inter-stage ring (the
previous stage's last-tick outbox, shipped by `MeshRouter.stage_shift`)
instead of the same-tick output of the previous layer; `router.psum`
still reduces over "data" only, so each stage's TickStats describe ITS
layers and the host unstacks them back into per-layer stats. Quiescence
and consistent-query silence use `router.psum_vote` (both axes) — a
single stage's quiet never terminates the pipeline while another stage
or the ring still holds work.

Windowing replaces "emit now" with deadline tables:
  inter-layer window -> delays the reduce of a source vertex (red_*),
  intra-layer window -> delays the forward/psi-emission of a master (fwd_*).

Counts follow Algorithm 1 exactly:
  addElement(e)   : contributes (x_sent[u], +1) iff u has already sent
  addElement(u.f) : first send emits (x_u, +1) over ALL out-edges
  updateElement   : emits (x_new - x_sent, 0) over all out-edges
so an aggregator count equals the number of in-edges whose source feature
has been seen — identical to the static oracle's in-degree once quiescent.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import aggregators
from repro.core import windowing as win
from repro.core.delivery import XlaDelivery
from repro.core.events import (EdgeBatch, FeatBatch, MsgBatch, ReplBatch,
                               coalesce_msg_batch, concat_msg_batches)
from repro.core.state import LayerState, TopoState, local_index
from repro.dist.router import LocalRouter, add_receipts


@dataclass(frozen=True)
class TickStats:
    broadcast_msgs: jnp.ndarray      # round-A replica messages
    reduce_msgs: jnp.ndarray         # round-B aggregator RMIs routed
    cross_part_msgs: jnp.ndarray     # messages leaving their part ("network")
    emitted: jnp.ndarray             # forward emissions to the next layer
    dropped: jnp.ndarray             # emissions deferred by outbox capacity
    # routing-plane wire telemetry (ISSUE 5) — MEASURED exchange counters,
    # psum'd over the mesh; all zero under LocalRouter / a 1-device mesh.
    # The emission counters above are counted at EMISSION time, so they
    # stay exactly equal across route_cap settings — these count the wire.
    # (Wire BYTES are a compile-time constant per tick and are accounted
    # host-side in exact ints: StreamMetrics.wire_bytes.)
    wire_rows: jnp.ndarray           # live records shipped on all_to_all
    route_deferred: jnp.ndarray      # records pushed to defer rings
    route_dropped: jnp.ndarray       # records lost to a FULL defer ring
    # delta gating (ISSUE 6): out-edge RMIs NOT emitted because the
    # source's cumulative un-sent delta stayed under delta_eps — the
    # message volume the gate saved this tick. Counted at emission time
    # like reduce_msgs (reduce_msgs + n_suppressed is invariant across
    # eps for a fixed send schedule); psum'd over the mesh; always 0 in
    # exact mode (delta_eps=0 compiles the gate away).
    n_suppressed: jnp.ndarray
    # compacted delivery: the lane rows the RMI delivery's steps covered
    # (deliver_chunk rows a step), psum'd over the mesh like wire_rows.
    # The lane's full capacity is a compile-time constant, accounted
    # host-side (StreamMetrics.deliver_lane_rows).
    deliver_rows: jnp.ndarray
    # telemetry plane (ISSUE 9) — occupancy gauges, static zeros unless
    # PipelineConfig.telemetry=True (XLA dead-code-eliminates them, so the
    # default program is bit-for-bit the five-plane tick). The defer-ring
    # gauges are END-OF-TICK ring populations (psum'd exact integers);
    # summed over a super-tick they become backlog INTEGRALS (ring-rows x
    # ticks, the same convention as QueryStats.held_ticks). route_peak is
    # the tick's max per-destination route-bucket demand BEFORE capping —
    # the zero-defer route_cap; its scan SUM is meaningless and unused
    # (per-tick values ride the trace's occupancy row instead).
    occ_bc_defer: jnp.ndarray        # rows waiting in broadcast defer rings
    occ_rmi_defer: jnp.ndarray       # rows waiting in RMI defer rings
    route_peak: jnp.ndarray          # peak per-dest bucket demand (pre-cap)
    # outbox_part_peak is the tick's max PER-PART eviction demand before
    # the outbox quota. The outbox cap binds per part (outbox_cap //
    # n_parts slots each, enforced by forward_psi's top_k), so the GLOBAL
    # demand (emitted + dropped) under-sizes the cap whenever demand is
    # skewed across parts — zero-drop needs
    # outbox_cap >= n_parts x outbox_part_peak. pmax'd across devices;
    # like route_peak its scan SUM is meaningless (per-tick values ride
    # the trace's occupancy row).
    outbox_part_peak: jnp.ndarray    # peak per-part outbox demand (pre-cap)
    busy: jnp.ndarray                # [P] per-part processed-event proxy


jax.tree_util.register_dataclass(
    TickStats, data_fields=["broadcast_msgs", "reduce_msgs",
                            "cross_part_msgs", "emitted", "dropped",
                            "wire_rows", "route_deferred",
                            "route_dropped", "n_suppressed",
                            "deliver_rows", "occ_bc_defer", "occ_rmi_defer",
                            "route_peak", "outbox_part_peak", "busy"],
    meta_fields=[])


def zero_stats(n_parts: int) -> TickStats:
    """Additive identity for TickStats — the summed carry of the super-tick
    scan starts here; dtypes must match what the tick body emits (int32 on
    the default 32-bit jnp) or the scan carry would be ill-typed. Under the
    mesh `n_parts` is the LOCAL part count (busy stays shard-local)."""
    z = jnp.zeros((), jnp.int32)
    return TickStats(broadcast_msgs=z, reduce_msgs=z, cross_part_msgs=z,
                     emitted=z, dropped=z, wire_rows=z,
                     route_deferred=z, route_dropped=z, n_suppressed=z,
                     deliver_rows=z, occ_bc_defer=z, occ_rmi_defer=z,
                     route_peak=z, outbox_part_peak=z,
                     busy=jnp.zeros((n_parts,), jnp.int32))


def add_stats(a: TickStats, b: TickStats) -> TickStats:
    return jax.tree.map(jnp.add, a, b)


# ===================================================== compute-plane stages

def round_a_apply(topo: TopoState, ls: LayerState, inbox: FeatBatch,
                  new_repl: ReplBatch, part0, delivery):
    """Round A, emit half: apply the inbox at LOCAL masters and build the
    broadcast MsgBatch for replication records whose master changed.

    Returns (feat_flat, changed, has_feat, bcast, busy, n_bcast, n_cross)
    — all [P_loc * N]-flat local arrays except the part-addressed bcast.
    """
    P_loc, N, d_in = ls.feat.shape
    busy = jnp.zeros((P_loc,), jnp.int32)

    in_idx, in_lp = local_index(inbox.part, inbox.slot, part0, P_loc, N,
                                inbox.valid)
    feat_flat = ls.feat.reshape(P_loc * N, d_in)
    # coalesce duplicate targets within the tick: last-writer-wins is fine
    # for idempotent feature values (both backends resolve duplicates that
    # way; valid inbox targets are unique anyway).
    feat_flat, changed = delivery.deliver_set(feat_flat, in_idx, inbox.feat)
    has_feat = ls.has_feat.reshape(P_loc * N) | changed
    busy = busy.at[in_lp].add(1, mode="drop")

    # replica-creation sync: a NEW replica immediately receives its master's
    # current state (the paper replicates state on placement, §5.1) — mark
    # the master "changed" so the broadcast below covers the new record;
    # only the new record fires because older replicas already hold the
    # value (idempotent re-set, coalesced by the same scatter).
    nr_idx, _ = local_index(new_repl.part, new_repl.master_slot, part0,
                            P_loc, N, new_repl.valid)
    nr_push = (nr_idx < P_loc * N) & has_feat[jnp.minimum(nr_idx,
                                                          P_loc * N - 1)]
    changed = changed.at[jnp.where(nr_push, nr_idx, P_loc * N)].set(
        True, mode="drop")

    # broadcast emission: replication records whose master changed this tick
    pp = jnp.arange(P_loc)[:, None]
    r_midx = pp * N + topo.r_master_slot                           # [Pl,R]
    r_live = topo.r_valid & changed[r_midx]
    src_part = jnp.broadcast_to(part0 + pp, r_live.shape)
    bcast = MsgBatch(
        part=topo.r_rep_part.reshape(-1),
        slot=topo.r_rep_slot.reshape(-1),
        vec=jnp.where(r_live.reshape(-1)[:, None],
                      feat_flat[r_midx.reshape(-1)], 0.0),
        cnt=jnp.zeros((r_live.size,), jnp.float32),
        src_part=src_part.reshape(-1),
        valid=r_live.reshape(-1))
    n_bcast = jnp.sum(r_live)
    n_cross = jnp.sum(r_live & (topo.r_rep_part != part0 + pp))
    return feat_flat, changed, has_feat, bcast, busy, n_bcast, n_cross


def round_b_emit(layer, params, topo: TopoState, ls: LayerState, feat_flat,
                 changed, has_feat, bcast_d: MsgBatch, new_edges: EdgeBatch,
                 now, wconf: win.WindowConfig, part0, busy, freq, delivery,
                 delta_eps: float = 0.0):
    """Round B, emit half: apply DELIVERED broadcasts at local replicas,
    decide which touched vertices send this tick (inter-layer window), and
    emit the tick's aggregator RMI records.

    delta_eps (static, ISSUE 6): delta-gated incremental propagation. A
    deadline-due vertex that has already sent only re-emits when its
    CUMULATIVE un-sent delta ||phi(x) - phi(x_sent)|| exceeds eps (per
    the layer's aggregator gate, core/aggregators.GATES — MAX/MIN use the
    grow-only monotonic short-circuit instead of the L2 norm). Suppressed
    vertices clear red_pending (they count as QUIET for termination) but
    keep their x_sent, so the residual accumulates and re-gates on the
    next touch: the un-sent delta per vertex is <= eps at every quiescent
    point, which bounds the synopsis error by eps. First sends and
    new-edge RMIs are never gated. delta_eps=0.0 (default) compiles the
    gate away — bit-for-bit the ungated program.

    Returns (feat_flat, changed, has_feat, x_sent_flat, has_sent,
    red_pending, red_deadline, rmis, busy, n_reduce, n_cross, n_supp).
    """
    P_loc, N, d_in = ls.feat.shape

    # delivered broadcasts land at local replicas (set semantics; targets
    # are unique — one master per replica, host-coalesced inbox)
    b_idx, b_lp = local_index(bcast_d.part, bcast_d.slot, part0, P_loc, N,
                              bcast_d.valid)
    feat_flat, b_touched = delivery.deliver_set(feat_flat, b_idx,
                                                bcast_d.vec)
    changed = changed | b_touched
    has_feat = has_feat | b_touched
    busy = busy.at[b_lp].add(1, mode="drop")

    x_sent_flat = ls.x_sent.reshape(P_loc * N, d_in)
    has_sent = ls.has_sent.reshape(P_loc * N)

    # new-edge RMIs (addElement(e), Alg. 1) — emitted by the part that owns
    # the edge record (it holds the source replica's x_sent)
    e_sidx, e_lp = local_index(new_edges.part, new_edges.src_slot, part0,
                               P_loc, N, new_edges.valid)
    e_local = e_sidx < P_loc * N
    e_gather = jnp.minimum(e_sidx, P_loc * N - 1)
    e_ready = e_local & has_sent[e_gather]                       # msgReady
    e_msg = layer.message(params, x_sent_flat[e_gather])
    busy = busy.at[e_lp].add(1, mode="drop")

    # per-vertex reduce/replace deltas under the inter-layer window
    red_pending = ls.red_pending.reshape(P_loc * N) | changed
    red_deadline = ls.red_deadline.reshape(P_loc * N)
    touched_deadline = win.next_deadline(
        wconf, now, red_deadline, ls.red_pending.reshape(P_loc * N), freq)
    red_deadline = jnp.where(changed, touched_deadline, red_deadline)
    # STREAMING evicts everything pending (incl. deadlines scheduled by a
    # previous windowed policy — the drain path of flush())
    cand = red_pending if wconf.kind == win.STREAMING else \
        red_pending & (red_deadline <= now)
    # sources: delta = phi(x) - phi(x_sent) if has_sent else (phi(x), +1)
    msg_new = layer.message(params, feat_flat)
    msg_old = layer.message(params, x_sent_flat)
    if delta_eps > 0.0:
        gate = aggregators.GATES[getattr(layer, "agg_kind", "mean")]
        suppress = cand & has_sent & gate(msg_new, msg_old, delta_eps)
        send = cand & ~suppress
    else:                       # exact mode: the gate is compiled away
        suppress = None
        send = cand
    delta_vec = jnp.where(send[:, None],
                          msg_new - jnp.where(has_sent[:, None], msg_old, 0.0),
                          0.0)
    delta_cnt = jnp.where(send, jnp.where(has_sent, 0.0, 1.0), 0.0)

    # per-edge gather of source deltas -> destination masters
    pp = jnp.arange(P_loc)[:, None]
    o_sidx = pp * N + topo.e_src_slot                            # [Pl,E]
    o_live = topo.e_valid & send[o_sidx]
    o_src_part = jnp.broadcast_to(part0 + pp, o_live.shape)
    e_rmis = MsgBatch(
        part=new_edges.dst_master_part, slot=new_edges.dst_master_slot,
        vec=jnp.where(e_ready[:, None], e_msg, 0.0),
        cnt=e_ready.astype(jnp.float32),
        src_part=new_edges.part, valid=e_ready)
    o_rmis = MsgBatch(
        part=topo.e_dst_mpart.reshape(-1), slot=topo.e_dst_mslot.reshape(-1),
        vec=jnp.where(o_live.reshape(-1)[:, None],
                      delta_vec[o_sidx.reshape(-1)], 0.0),
        cnt=delta_cnt[o_sidx.reshape(-1)] * o_live.reshape(-1),
        src_part=o_src_part.reshape(-1), valid=o_live.reshape(-1))
    rmis = concat_msg_batches(e_rmis, o_rmis)
    n_reduce = jnp.sum(e_ready) + jnp.sum(o_live)
    n_cross = (jnp.sum(e_ready
                       & (new_edges.dst_master_part != new_edges.part))
               + jnp.sum(o_live & (topo.e_dst_mpart != part0 + pp)))

    # commit send bookkeeping; suppressed vertices leave the pending set
    # WITHOUT advancing x_sent — the residual delta stays accumulated
    # against the last value actually emitted, so a later touch re-gates
    # the cumulative delta (and quiescence sees a quiet vertex meanwhile)
    x_sent_flat = jnp.where(send[:, None], feat_flat, x_sent_flat)
    has_sent = has_sent | send
    if suppress is None:
        red_pending = red_pending & ~send
        n_supp = jnp.zeros((), jnp.int32)
    else:
        red_pending = red_pending & ~send & ~suppress
        # saved message volume = the out-edge RMIs the gate skipped
        n_supp = jnp.sum(topo.e_valid & suppress[o_sidx])
    return (feat_flat, changed, has_feat, x_sent_flat, has_sent,
            red_pending, red_deadline, rmis, busy, n_reduce, n_cross,
            n_supp)


def deliver_chunk(capacity: int) -> int:
    """Rows a delivered RMI lane of `capacity` rows is delivered in per
    step: capacity / 64 rounded up to a multiple of 1,024, at most the
    whole lane. A function of the lane's shape alone, so every router,
    driver and backend compiles the same delivery."""
    k = -(-capacity // 64)
    return min(capacity, -(-k // 1024) * 1024)


def deliver_rmis(ls: LayerState, rmis_d: MsgBatch, part0, busy, delivery,
                 n_parts: int):
    """Apply DELIVERED aggregator RMIs at local masters (flat scatter-add
    on "xla", sorted segment reduction on "pallas"), over the lane's LIVE
    rows only, whatever the reduce/replace/remove mix.

    Canonical order: the all_to_all concatenates arrivals by SOURCE
    DEVICE, so the order in which two records from different shards reach
    the same aggregator depends on the device count — the one place the
    mesh program's f32 sums depend on D. Rows from the SAME source part
    always arrive in that part's emission order (route_pack and the defer
    rings are order-preserving), so (source part, arrival) is a TOTAL
    canonical order, and every aggregator receives its records in it:
    uncapped mesh runs are bit-equal across any device count, which lets
    a live reshard (D -> D') be verified against the uninterrupted run
    with assert_array_equal rather than allclose.

    Compaction: a delivered lane is mostly padding (the dense wire hands
    each device D x the emission capacity a tick; on one device the lane
    is the whole emission capacity). A cumsum per source part over the
    lane's live flags gives each live row its canonical slot, and one
    scatter lists the live rows in that order. The delivery then runs in
    steps of `deliver_chunk(C)` rows over as many steps as the live rows
    need (none on a quiet tick), so every load is delivered exactly:
    nothing is capped, dropped or deferred. A scatter-add applies records
    in order, so on "xla" the steps give the single delivery's sums bit
    for bit; "pallas" sums each step's records before adding them, so
    past one step its sums round differently (as they differ from
    "xla"'s).

    Returns (agg_flat, cnt_flat, agg_dirty, busy, n_rows) — n_rows is the
    lane rows the delivery's steps covered."""
    P_loc, N, d_agg = ls.agg.shape
    C = rmis_d.capacity
    G = deliver_chunk(C)
    idx, lp = local_index(rmis_d.part, rmis_d.slot, part0, P_loc, N,
                          rmis_d.valid)
    live = lp < P_loc
    src = jnp.clip(rmis_d.src_part, 0, n_parts - 1)
    # canonical slot of each live row: the live rows of every lower source
    # part first, then its rank among its own part's live rows
    of_part = src[None, :] == jnp.arange(n_parts, dtype=src.dtype)[:, None]
    rank = jnp.cumsum((live[None, :] & of_part).astype(jnp.int32), axis=1)
    per_part = rank[:, -1]
    first = jnp.cumsum(per_part) - per_part
    pos = first[src] + jnp.sum(jnp.where(of_part, rank, 0), axis=0) - 1
    n_chunks = -(-C // G)
    rows = jnp.full((n_chunks * G,), C, jnp.int32).at[
        jnp.where(live, pos, n_chunks * G)].set(
            jnp.arange(C, dtype=jnp.int32), mode="drop")

    def chunk(i, carry):
        agg, cnt, dirty = carry
        r = jax.lax.dynamic_slice(rows, (i * G,), (G,))
        pad = r == C
        r = jnp.minimum(r, C - 1)
        agg, cnt, d = delivery.deliver_add(
            agg, cnt, jnp.where(pad, P_loc * N, idx[r]), rmis_d.vec[r],
            rmis_d.cnt[r])
        return agg, cnt, dirty | d

    took = (jnp.sum(per_part) + G - 1) // G
    agg, cnt, dirty = jax.lax.fori_loop(
        0, took, chunk, (ls.agg.reshape(P_loc * N, d_agg),
                         ls.agg_cnt.reshape(P_loc * N),
                         jnp.zeros((P_loc * N,), bool)))
    busy = busy + jnp.sum(lp[None, :] == jnp.arange(P_loc)[:, None], axis=1,
                          dtype=jnp.int32)
    return agg, cnt, dirty, busy, jnp.minimum(took * G, C)


def forward_psi(layer, params, topo: TopoState, ls: LayerState, feat_flat,
                has_feat, agg_flat, cnt_flat, agg_dirty, changed, now,
                wconf: win.WindowConfig, outbox_cap_pp: int, part0, busy,
                freq, delivery):
    """Forward/update phase (psi) under the intra-layer window, with a
    PER-PART capacity-limited outbox (first `outbox_cap_pp` evicted slots
    per part emit; the rest stay pending -> natural backpressure).

    Returns (fwd_pending, fwd_deadline, outbox, busy, n_emit, n_drop,
    n_demand_pp) — n_demand_pp is the max per-part eviction demand
    BEFORE the quota (the zero-drop per-part outbox size; DCE'd by XLA
    when the telemetry plane is off)."""
    P_loc, N, _ = ls.feat.shape
    is_m = topo.is_master.reshape(P_loc * N)
    dirty = (agg_dirty | (changed & is_m)) & has_feat & is_m
    fwd_pending = ls.fwd_pending.reshape(P_loc * N) | dirty
    fwd_deadline = ls.fwd_deadline.reshape(P_loc * N)
    fwd_touch_dl = win.next_deadline(
        wconf, now, fwd_deadline, ls.fwd_pending.reshape(P_loc * N), freq)
    fwd_deadline = jnp.where(dirty, fwd_touch_dl, fwd_deadline)
    evict = fwd_pending if wconf.kind == win.STREAMING else \
        fwd_pending & (fwd_deadline <= now)

    n_demand_pp = jnp.max(jnp.sum(evict.reshape(P_loc, N), axis=1,
                                  dtype=jnp.int32))
    order = jnp.where(evict.reshape(P_loc, N),
                      jnp.arange(N)[None, :], N)                # [Pl,N]
    k = max(1, min(outbox_cap_pp, N))
    picked = jax.lax.top_k(-order, k)[0] * -1                   # ascending
    picked_valid = picked < N                                   # [Pl,k]
    picked = jnp.minimum(picked, N - 1)
    flat_picked = (jnp.arange(P_loc)[:, None] * N + picked).reshape(-1)
    # invalid picks go to the OOB sentinel, NOT clamped onto slot N-1: a
    # duplicate-index scatter-set of (True, False) can resolve to False
    # and silently erase the emission (fwd_pending then never clears)
    mask_idx = jnp.where(picked_valid.reshape(-1), flat_picked, P_loc * N)
    emitted_mask = jnp.zeros((P_loc * N,), bool).at[mask_idx].set(
        True, mode="drop")
    deferred = evict & ~emitted_mask
    n_emit = jnp.sum(emitted_mask)
    n_drop = jnp.sum(deferred)

    x_self = feat_flat[flat_picked]
    agg_read = delivery.agg_read_rows(agg_flat, cnt_flat, flat_picked)
    x_out = layer.update(params, x_self, agg_read)
    out_part = jnp.broadcast_to(part0 + jnp.arange(P_loc)[:, None],
                                picked.shape)
    outbox = FeatBatch(part=out_part.reshape(-1).astype(jnp.int32),
                       slot=picked.reshape(-1).astype(jnp.int32),
                       feat=x_out, valid=picked_valid.reshape(-1))
    fwd_pending = fwd_pending & ~emitted_mask
    busy = busy + jnp.sum(picked_valid, axis=1, dtype=jnp.int32)
    return (fwd_pending, fwd_deadline, outbox, busy, n_emit, n_drop,
            n_demand_pp)


# ======================================================== the full tick body

def layer_tick_body(layer, params, topo: TopoState, ls: LayerState,
                    inbox: FeatBatch, new_edges: EdgeBatch,
                    new_repl: ReplBatch, now: jnp.ndarray,
                    wconf: win.WindowConfig, outbox_cap: int, router=None,
                    delivery=None, extra_lane=None, delta_eps: float = 0.0,
                    telemetry: bool = False):
    """Advance one GNN layer by one tick (pure, trace-friendly).

    `layer` supplies message/update (phi/psi): layer.message(params, x) and
    layer.update(params, x_self, agg_read) — e.g. graph/sage.SAGELayer.
    `router` owns cross-part transport (default: LocalRouter over the full
    part axis); `delivery` owns how routed records land in state (default:
    the XLA scatter reference, see core/delivery.py). `outbox_cap` is the
    GLOBAL per-tick emission budget; each part gets outbox_cap //
    router.n_parts slots.

    extra_lane: optional (batch, (defer_rows, defer_ok)) — one extra
    part-addressed lane FUSED into this layer's round-B exchange (same
    all_to_all launch; ISSUE 5 lane fusion). The pipeline rides the query
    plane's link-score wire on layer 0 this way.

    telemetry (static, ISSUE 9): when True the TickStats occupancy gauges
    (occ_bc_defer / occ_rmi_defer / route_peak) carry exact measured
    integers; when False (default) they are static zeros and XLA compiles
    the gauge arithmetic away — bit-for-bit the untraced tick.

    delta_eps (static): delta-gated propagation (ISSUE 6, see
    round_b_emit). In approximate mode (> 0) the tick additionally
    COALESCES same-destination RMI records before the routing plane, so
    a hub master that many gated sources touch in one tick receives one
    pre-summed record — fewer live rows through the capped all_to_all
    and the defer rings (coalescing reorders f32 sums, which is why the
    exact eps=0 program skips it and stays bit-identical to PR 5).

    Returns (new LayerState, outbox FeatBatch, TickStats, extra_out) —
    stats scalars are router.psum'd (global), `busy` stays local [P_loc];
    extra_out is None, or (delivered extra lane, its new defer ring).

    This is the un-jitted body so the super-tick driver can inline all L
    layers inside one `lax.scan` step (and the mesh path can wrap the whole
    program in one `shard_map`); the per-tick reference path wraps it in
    `layer_tick` below.
    """
    if router is None:
        router = LocalRouter(n_parts=ls.feat.shape[0])
    if delivery is None:
        delivery = XlaDelivery()
    part0 = router.part0()
    P_loc, N, d_in = ls.feat.shape
    cap_pp = max(1, outbox_cap // router.n_parts)

    keys = part0 * N + jnp.arange(P_loc * N)          # global CMS keys
    freq = win.cms_query(ls.cms, keys) if wconf.kind == win.ADAPTIVE \
        else jnp.zeros((P_loc * N,), jnp.float32)

    # ---- Round A: apply inbox at masters, emit + route the broadcast
    with jax.named_scope("d3.round_a"):
        (feat_flat, changed, has_feat, bcast, busy,
         n_bcast, bcast_cross) = round_a_apply(topo, ls, inbox, new_repl,
                                               part0, delivery)
    with jax.named_scope("d3.route"):
        (bcast_d,), (bc_defer,), rcpt = router.route_lanes(
            (bcast,), ((ls.bc_defer, ls.bc_defer_ok),))

    # ---- Round B: apply broadcast at replicas, emit + route the RMIs
    # (the optional extra lane shares this exchange's single all_to_all)
    with jax.named_scope("d3.round_b"):
        (feat_flat, changed, has_feat, x_sent_flat, has_sent, red_pending,
         red_deadline, rmis, busy, n_reduce, red_cross,
         n_supp) = round_b_emit(
            layer, params, topo, ls, feat_flat, changed, has_feat, bcast_d,
            new_edges, now, wconf, part0, busy, freq, delivery,
            delta_eps=delta_eps)
        if delta_eps > 0.0:
            # approximate mode only: coalesce same-destination additive
            # RMIs before the outbox/routing plane (stats above counted
            # pre-coalesce)
            rmis = coalesce_msg_batch(rmis, N)
    rmi_defer_in = (ls.rmi_defer, ls.rmi_defer_ok)
    with jax.named_scope("d3.route"):
        if extra_lane is None:
            (rmis_d,), (rmi_defer,), rcpt_b = router.route_lanes(
                (rmis,), (rmi_defer_in,))
            extra_out = None
        else:
            xbatch, xdefer = extra_lane
            (rmis_d, extra_d), (rmi_defer, xdefer_new), rcpt_b = \
                router.route_lanes((rmis, xbatch), (rmi_defer_in, xdefer))
            extra_out = (extra_d, xdefer_new)
        rcpt = add_receipts(rcpt, rcpt_b)

    # ---- apply RMIs at local masters (live rows only, in canonical order:
    # the additive scatter is the one delivery whose f32 result depends on
    # arrival order, and arrival order is the one thing that depends on D)
    with jax.named_scope("d3.deliver"):
        agg_flat, cnt_flat, agg_dirty, busy, n_deliver = deliver_rmis(
            ls, rmis_d, part0, busy, delivery, router.n_parts)

    # ---- forward/update phase (psi), intra-layer window
    with jax.named_scope("d3.forward"):
        (fwd_pending, fwd_deadline, outbox, busy,
         n_emit, n_drop, n_demand_pp) = forward_psi(
            layer, params, topo, ls, feat_flat, has_feat, agg_flat,
            cnt_flat, agg_dirty, changed, now, wconf, cap_pp, part0, busy,
            freq, delivery)

    # ---- adaptive-session CMS update (sketch replicated across devices:
    # local contributions are psum'd so every device applies the same add)
    cms = ls.cms
    if wconf.kind == win.ADAPTIVE:
        touch_keys = jnp.where(changed, keys, 0)
        delta = win.cms_delta(cms.shape, touch_keys,
                              changed.astype(jnp.float32))
        cms = cms * wconf.cms_decay + router.psum(delta)

    d_agg = agg_flat.shape[-1]
    new_ls = LayerState(
        feat=feat_flat.reshape(P_loc, N, d_in),
        has_feat=has_feat.reshape(P_loc, N),
        x_sent=x_sent_flat.reshape(P_loc, N, d_in),
        has_sent=has_sent.reshape(P_loc, N),
        agg=agg_flat.reshape(P_loc, N, d_agg),
        agg_cnt=cnt_flat.reshape(P_loc, N),
        red_pending=red_pending.reshape(P_loc, N),
        red_deadline=red_deadline.reshape(P_loc, N),
        fwd_pending=fwd_pending.reshape(P_loc, N),
        fwd_deadline=fwd_deadline.reshape(P_loc, N),
        cms=cms,
        last_touch=jnp.where(changed, now,
                             ls.last_touch.reshape(P_loc * N)
                             ).reshape(P_loc, N),
        bc_defer=bc_defer[0], bc_defer_ok=bc_defer[1],
        rmi_defer=rmi_defer[0], rmi_defer_ok=rmi_defer[1])
    psum = router.psum
    if telemetry:
        occ_bc = psum(jnp.sum(bc_defer[1].astype(jnp.int32)))
        occ_rmi = psum(jnp.sum(rmi_defer[1].astype(jnp.int32)))
        route_peak = router.pmax(rcpt.peak)
        outbox_pp = router.pmax(n_demand_pp)
    else:
        occ_bc = occ_rmi = route_peak = outbox_pp = jnp.zeros((), jnp.int32)
    stats = TickStats(broadcast_msgs=psum(n_bcast),
                      reduce_msgs=psum(n_reduce),
                      cross_part_msgs=psum(bcast_cross + red_cross),
                      emitted=psum(n_emit), dropped=psum(n_drop),
                      wire_rows=psum(rcpt.rows),
                      route_deferred=psum(rcpt.deferred),
                      route_dropped=psum(rcpt.dropped),
                      n_suppressed=psum(n_supp),
                      deliver_rows=psum(n_deliver),
                      occ_bc_defer=occ_bc, occ_rmi_defer=occ_rmi,
                      route_peak=route_peak, outbox_part_peak=outbox_pp,
                      busy=busy)
    return new_ls, outbox, stats, extra_out


layer_tick = partial(jax.jit, static_argnames=("layer", "wconf", "outbox_cap",
                                               "router", "delivery",
                                               "delta_eps", "telemetry")
                     )(layer_tick_body)


def has_work(ls: LayerState) -> jnp.ndarray:
    """Termination-detection predicate: any pending timer, unsent delta, or
    route-deferred record still waiting in a backpressure ring (carried
    wire rows are in-flight work — quiescence must not fire over them)."""
    return (jnp.any(ls.red_pending) | jnp.any(ls.fwd_pending)
            | jnp.any(ls.bc_defer_ok) | jnp.any(ls.rmi_defer_ok))
