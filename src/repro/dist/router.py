"""The routing plane: transport of part-addressed record batches.

The streaming tick is split into six planes (ISSUE 2-5, 8, 9):

  * COMPUTE plane — pure part-local stages in `core/tick.py`
    (`round_a_apply`, `round_b_emit`, `deliver_rmis`, `forward_psi`) that
    never write into another part's rows; every cross-part effect is a
    `MsgBatch` (core/events.py) addressed by global (part, slot).
  * ROUTING plane — a Router moves those records to whichever device
    owns the destination part. Two golden-equivalent implementations:

      LocalRouter : one device owns every part; transport is the identity.
      MeshRouter  : parts are block-sharded over a 1-D ("data",) mesh axis
                    (`launch/mesh.py`); transport compacts records by
                    destination device and exchanges them with ONE
                    `lax.all_to_all` per `route_lanes` call — ALL fields
                    of ALL lanes in the call ride a single packed wire
                    buffer (`dist/wire.py`), so a MsgBatch round costs one
                    collective launch instead of one per field, and the
                    round-B RMI lane + the query-plane wire lane share one
                    launch per tick (ISSUE 5 lane fusion).
  * DELIVERY plane — once routed, a DeliveryBackend (`core/delivery.py`)
    lands the records in the local state blocks: "xla" reference scatters
    or "pallas" sorted segment-reduce kernels, selected by
    `PipelineConfig.delivery_backend` and orthogonal to the Router choice.
  * QUERY plane — `repro/serve/query.py` answers point queries from the
    state the other three maintain; its link-score wire hop rides
    `route_lanes` fused with layer 0's round-B exchange.
  * TRAINING plane — `repro/core/train_plane.py` (ISSUE 8) runs a
    windowed online training step at the end of the tick; its layered
    backward ships dL/dagg to replicas and folds replica gradients onto
    masters through two dense `route_lanes` calls per layer, and its
    parameter averaging (Alg. 3) rides `psum`.
  * TELEMETRY plane — `repro/telemetry/` (ISSUE 9) watches the other
    five: with `MeshRouter.telemetry=True` each exchange also reports
    its peak pre-cap bucket demand (`RouteReceipt.peak`, the zero-defer
    route_cap), reduced over the mesh with `pmax`/`pmax_stage`.

Hybrid parallelism (ISSUE 7): on a 2-D ("stage", "data") mesh the L GNN
layers are placed round-robin on the stage axis (layer l lives on stage
l % S) and MeshRouter gains a second, inter-stage lane: `stage_shift`
posts each round's outbox to the next stage with one circular
`lax.ppermute` immediately after that round's compute (double-buffered —
the hop for round r overlaps round r+1's intra-stage all_to_all), and
`stage_last` rides the final stage's exchange back so every stage can
apply the same sink update. All data-plane collectives (`route_lanes`,
`psum`, `part0`) stay scoped to the "data" axis — inside a stage row
they behave exactly as on the 1-D mesh — while quiescence/silence VOTES
go through `psum_vote` (both axes) so no stage can declare the dataflow
quiet while another still has records in flight.

Traffic-adaptive capped exchange (ISSUE 5 tentpole): the per-destination
send bucket holds `route_cap` rows (default None = the lane's full
emission capacity C — the pre-ISSUE-5 worst-case sizing, under which no
record can ever overflow and the exchange is bit-for-bit the dense one).
With `route_cap < C` the wire shrinks from D x C to D x cap rows per
lane; live records that overflow their bucket are NOT dropped — they are
deferred into a per-lane carry ring (packed rows riding the
`PipelineCarry`, see `dist/wire.py:init_defer`) and re-enter the next
tick's exchange AHEAD of fresh emissions (FIFO per destination, which
keeps feature-broadcast ordering intact). Quiescence voting counts defer
occupancy as pending work (`core/tick.py:has_work`), so a flush never
terminates with records still in flight. Only a defer ring that is
ITSELF full drops rows, and loudly: the per-tick `RouteReceipt.dropped`
count surfaces in TickStats/StreamMetrics — size `route_defer_cap`
accordingly (default: one full emission capacity per lane).

Delta-gated traffic (ISSUE 6): in approximate mode (cfg.delta_eps > 0)
the compute plane suppresses sub-eps re-emissions AND pre-coalesces
same-destination RMI records before handing the lane to `route_lanes`
(`core/events.py:coalesce_msg_batch`), so the capped buckets see one
live row per distinct destination master instead of one per out-edge.
TickStats.reduce_msgs/n_suppressed count at EMISSION time (pre-
coalesce); RouteReceipt.rows counts the wire — their gap is the
coalescing win, visible in `benchmarks/bench_delta_gating.py`.

Compaction uses `kernels/route_pack`: one stable sort by destination +
rank-from-run-start (replacing the O(C * D) one-hot membership cumsum),
with the placement scatter runnable as a Pallas one-hot-MXU pass
(`pack_backend="pallas"`, reusing the segment_reduce machinery) or a
plain XLA scatter (`"xla"`). Invalid destination parts are MASKED OUT of
the exchange (pre-ISSUE-5 the `jnp.clip(part // Pl, 0, D-1)` silently
misrouted them to the last device, where they burned bucket capacity
before being dropped at delivery).

Device planes: the caller's `d3.route` scope covers packing and
unpacking; the collective alone (the all_to_all, and the stage axis's
ppermute) runs under `d3.wire`, so a profiler trace tells the exchange
apart from its packing.

Routers are small frozen dataclasses so they can ride jit boundaries as
static arguments. `MeshRouter` methods are only valid INSIDE a
`shard_map` over its axis (they call `lax.axis_index`/`lax.all_to_all`);
`LocalRouter` works anywhere. `psum` abstracts the cross-device reduction
used for scalar TickStats, quiescence voting and the replicated
CountMinSketch update (identity on one device).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.wire import field_col, pack_lane, unpack_lane
from repro.kernels.route_pack.ops import route_pack, route_plan


@dataclass(frozen=True)
class RouteReceipt:
    """Measured wire telemetry of one route_lanes call (int32 scalars,
    local to the calling device — the tick body psums them into
    TickStats so StreamMetrics reports EXACT exchanged rows).

      rows     : live records actually shipped on the wire this call;
      deferred : live records pushed into the defer rings (backpressure);
      dropped  : live records lost to a FULL defer ring (loud — see
                 module docstring; 0 in any correctly-sized config);
      peak     : telemetry plane (ISSUE 9) — the call's MAX per-
                 destination bucket demand BEFORE capping (carried +
                 fresh live rows aimed at the busiest device). This is
                 the zero-defer route_cap for the traffic the call saw;
                 static 0 unless MeshRouter.telemetry is set. Combined
                 across calls with `jnp.maximum` (see add_receipts) — a
                 peak gauge, never a sum.

    Wire BYTES are deliberately absent: the send-buffer size of a
    route_lanes call is a compile-time constant of (lanes, caps), so the
    pipeline accounts bytes host-side in exact int arithmetic
    (`D3Pipeline._static_wire_bytes`) instead of rounding them through a
    device float or overflowing an int32.
    """
    rows: jnp.ndarray
    deferred: jnp.ndarray
    dropped: jnp.ndarray
    peak: jnp.ndarray


jax.tree_util.register_dataclass(
    RouteReceipt, data_fields=["rows", "deferred", "dropped", "peak"],
    meta_fields=[])


def zero_receipt() -> RouteReceipt:
    z = jnp.zeros((), jnp.int32)
    return RouteReceipt(rows=z, deferred=z, dropped=z, peak=z)


def add_receipts(a: RouteReceipt, b: RouteReceipt) -> RouteReceipt:
    """Field-wise combine: counters add, the peak gauge maxes (summing a
    per-call maximum would be meaningless)."""
    return RouteReceipt(rows=a.rows + b.rows,
                        deferred=a.deferred + b.deferred,
                        dropped=a.dropped + b.dropped,
                        peak=jnp.maximum(a.peak, b.peak))


@dataclass(frozen=True)
class LocalRouter:
    """Single-device router: every part is local, delivery is identity."""
    n_parts: int

    @property
    def n_devices(self) -> int:
        return 1

    @property
    def n_local_parts(self) -> int:
        return self.n_parts

    def part0(self):
        """Global id of the first locally-owned part."""
        return jnp.int32(0)

    def route(self, msg):
        return msg

    def route_lanes(self, lanes, defers):
        """No wire: lanes deliver as-is, defer rings stay empty (they are
        zero-capacity under this router — see core/pipeline.py)."""
        return tuple(lanes), tuple(defers), zero_receipt()

    def psum(self, x):
        return x

    def pmax(self, x):
        return x

    # stage-axis interface (trivial here: LocalRouter never runs with
    # n_stages > 1 — PipelineConfig.validate rejects the combination —
    # but shared code paths in serve/termination call these)
    n_stages = 1

    def psum_stage(self, x):
        return x

    def pmax_stage(self, x):
        return x

    def psum_vote(self, x):
        return x

    def stage_gather(self, x):
        """All stages' copies of `x`, leading [S] axis ([1] here)."""
        return x[None]


@dataclass(frozen=True)
class MeshRouter:
    """Sharded router: parts block-sharded over `axis`, packed capped
    all_to_all delivery.

    Device d owns parts [d * Pl, (d + 1) * Pl) with Pl = n_parts
    // n_devices (validated by PipelineConfig.validate). Must run inside a
    shard_map over `axis` whose size is exactly `n_devices`.

    route_cap   : per-destination send-bucket rows (None = each lane's
                  full capacity — never-overflow dense semantics).
    pack_backend: how route_pack places rows into the send buffer
                  ("xla" scatter | "pallas" one-hot MXU pass); follows
                  PipelineConfig.delivery_backend.
    stage_axis  : name of the pipeline-stage mesh axis, or None on the
                  1-D mesh. n_devices always counts the DATA axis only —
                  parts shard within a stage row, never across stages.
    telemetry   : telemetry plane (ISSUE 9) — when True each route_lanes
                  call also measures its peak per-destination bucket
                  demand pre-cap (RouteReceipt.peak); when False (the
                  default) the gauge is a static 0 and the measurement
                  compiles away, keeping the exchange bit-for-bit.
    """
    n_parts: int
    n_devices: int
    axis: str = "data"
    route_cap: Optional[int] = None
    pack_backend: str = "xla"
    stage_axis: Optional[str] = None
    n_stages: int = 1
    telemetry: bool = False

    @property
    def n_local_parts(self) -> int:
        return self.n_parts // self.n_devices

    def part0(self):
        return lax.axis_index(self.axis).astype(jnp.int32) * \
            jnp.int32(self.n_local_parts)

    def psum(self, x):
        return lax.psum(x, self.axis)

    def pmax(self, x):
        """Max-reduce over the data axis (peak gauges, ISSUE 9)."""
        return lax.pmax(x, self.axis)

    # ---- stage-axis interface (hybrid parallelism, ISSUE 7) ----------
    # Valid inside a shard_map that names `stage_axis`; on a 1-D router
    # (stage_axis=None) every method degrades to its data-plane
    # counterpart so shared call sites trace the exact pre-ISSUE-7 HLO.

    def psum_stage(self, x):
        """Reduce over the stage axis only (identity on a 1-D mesh)."""
        if self.stage_axis is None:
            return x
        return lax.psum(x, self.stage_axis)

    def pmax_stage(self, x):
        """Max-reduce over the stage axis only (identity on a 1-D mesh) —
        peak gauges cross the stage axis with max, never sum."""
        if self.stage_axis is None:
            return x
        return lax.pmax(x, self.stage_axis)

    def psum_vote(self, x):
        """Global reduction for quiescence/silence votes: both axes on a
        2-D mesh, plain data psum on a 1-D mesh."""
        if self.stage_axis is None:
            return lax.psum(x, self.axis)
        return lax.psum(x, (self.stage_axis, self.axis))

    def stage_index(self):
        return lax.axis_index(self.stage_axis).astype(jnp.int32)

    def stage_shift(self, rows):
        """Post packed rows to the next stage: one circular ppermute
        (stage s -> s + 1 mod S) within each data column. Called right
        after each round's compute so the hop is double-buffered behind
        the next round's work."""
        S = self.n_stages
        with jax.named_scope("d3.wire"):
            return lax.ppermute(rows, self.stage_axis,
                                [(i, (i + 1) % S) for i in range(S)])

    def stage_last(self, rows):
        """Every stage's copy of the LAST stage's rows (the final GNN
        layer lives on stage S-1; its outbox must reach every stage's
        replicated sink/serve plane in the same tick)."""
        return lax.all_gather(rows, self.stage_axis)[self.n_stages - 1]

    def stage_gather(self, x):
        """Every stage's copy of `x`, leading [S] axis — the training
        plane gathers all rounds' layer caches so each stage row can run
        the full (stage-replicated) layered backward."""
        if self.stage_axis is None:
            return x[None]
        return lax.all_gather(x, self.stage_axis)

    def lane_cap(self, capacity: int) -> int:
        """Resolved per-destination bucket rows for a lane of the given
        local emission capacity."""
        if self.route_cap is None:
            return capacity
        return max(1, min(self.route_cap, capacity))

    def route_lanes(self, lanes, defers):
        """Deliver several record lanes with ONE all_to_all.

        lanes : tuple of part-addressed batch pytrees with `part`/`valid`
                fields (MsgBatch, QueryBatch, ...), local capacities C_i.
        defers: matching tuple of (packed rows [K_i, W_i] f32, occupied
                [K_i] bool) carry rings; K_i = 0 disables backpressure
                for that lane (then bucket overflow — impossible at the
                dense default — would drop, counted).

        Per lane: carried rows re-enter FIRST, fresh emissions after
        (stable destination sort keeps FIFO per destination, so a
        replica's feature broadcasts always apply in emission order);
        the first `lane_cap(C_i)` records per destination ship, the rest
        defer. Send buffers are concatenated along the row axis so the
        whole call is a single [D, sum_i cap_i * W_i] tiled all_to_all.

        Returns (delivered lanes tuple — capacity D * cap_i each, block
        j = what device j sent here, rank order within a block = source
        emission order; new defers tuple; RouteReceipt).
        """
        D = self.n_devices
        if D == 1:
            return tuple(lanes), tuple(defers), zero_receipt()
        Pl = self.n_local_parts

        sends, metas, new_defers = [], [], []
        n_ship = jnp.zeros((), jnp.int32)
        n_defer = jnp.zeros((), jnp.int32)
        n_drop = jnp.zeros((), jnp.int32)
        n_peak = jnp.zeros((), jnp.int32)
        for lane, (dbuf, dok) in zip(lanes, defers):
            packed = pack_lane(lane)                           # [C, W]
            C, W = packed.shape
            K = dbuf.shape[0]
            cap = self.lane_cap(C)
            allp = jnp.concatenate([dbuf, packed]) if K else packed
            parts = allp[:, field_col(lane, "part")].astype(jnp.int32)
            # mask invalid destinations OUT of the exchange (never clip
            # onto the last device) — deferred rows only ever hold valid
            # records, their occupancy flag is the live mask
            fresh_ok = (lane.valid & (lane.part >= 0)
                        & (lane.part < self.n_parts))
            ok = jnp.concatenate([dok, fresh_ok]) if K else fresh_ok
            dst = jnp.where(ok, parts // Pl, D)
            if self.telemetry:
                # peak per-destination demand BEFORE capping: the
                # route_cap at which this lane would never defer
                demand = jnp.zeros((D,), jnp.int32).at[dst].add(
                    ok.astype(jnp.int32), mode="drop")
                n_peak = jnp.maximum(n_peak, jnp.max(demand))

            order, ship_s, slot_s, left_s = route_plan(dst, ok, D, cap)
            rows_s = allp[order]
            send = route_pack(rows_s, slot_s, D * cap,
                              backend=self.pack_backend)       # [D*cap, W]
            sends.append(send.reshape(D, cap * W))
            metas.append((lane, cap, W))
            n_ship = n_ship + jnp.sum(ship_s.astype(jnp.int32))

            if K:
                lrank = jnp.cumsum(left_s.astype(jnp.int32)) - 1
                keep = left_s & (lrank < K)
                didx = jnp.where(keep, lrank, K)
                nbuf = jnp.zeros_like(dbuf).at[didx].set(rows_s,
                                                         mode="drop")
                nok = jnp.zeros((K,), bool).at[didx].set(True, mode="drop")
                new_defers.append((nbuf, nok))
                n_defer = n_defer + jnp.sum(keep.astype(jnp.int32))
                n_drop = n_drop + jnp.sum((left_s & ~keep
                                           ).astype(jnp.int32))
            else:
                new_defers.append((dbuf, dok))
                n_drop = n_drop + jnp.sum(left_s.astype(jnp.int32))

        buf = jnp.concatenate(sends, axis=1)                   # [D, X]
        with jax.named_scope("d3.wire"):
            got = lax.all_to_all(buf, self.axis, split_axis=0,
                                 concat_axis=0, tiled=True)    # [D, X]
        outs, off = [], 0
        for proto, cap, W in metas:
            blk = got[:, off:off + cap * W].reshape(D * cap, W)
            off += cap * W
            outs.append(unpack_lane(blk, proto))
        receipt = RouteReceipt(rows=n_ship, deferred=n_defer,
                               dropped=n_drop, peak=n_peak)
        return tuple(outs), tuple(new_defers), receipt
