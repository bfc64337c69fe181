"""The D3-GNN dataflow pipeline driver (paper Fig. 1).

Dataset -> Partitioner -> Splitter -> GraphStorage_1 .. GraphStorage_L -> sink

The host side plays Dataset/Partitioner/Splitter: it cuts the stream into
micro-ticks, assigns parts/slots (partitioner.py) and builds padded device
batches. The device side runs one tick per GraphStorage operator per tick;
layer l's outbox is layer l+1's inbox (the unrolled computation graph). The
final outbox materializes into a device-side embedding sink — the paper's
"materialized embedding table that can be further queried".

One driver launches ONE device program (`_tick_program`: topology apply +
L staged layer ticks + sink update, all over the local part block) as the
device-resident SUPER-TICK (the paper's always-on unrolled dataflow):
`run_super_tick()` has the host pre-stage T micro-ticks of padded batches
(stacked along a leading T axis, one transfer per field), then a single
jitted `jax.lax.scan` advances all L layers through all T ticks with the
`PipelineCarry` donated at the jit boundary and ONE host sync per
super-tick (the summed stats + quiescence flag read). A launch the host
need not answer before the next one stays PENDING: its sync comes after
the next launch has been staged and dispatched, so the host stages
super-tick k+1 while the device runs k (`run_super_tick` says when a
launch syncs at once and what settles a pending one). `tick()`,
`run_stream()` and `flush()` are that launch at T = 1, which is read at
once. The golden-equivalence tests pin T = 1 and T = k launches to the
static oracle.

Distributed execution: pass `mesh=` (a 1-D ("data",) mesh, see
`launch/mesh.py:make_stream_mesh`) and the SAME program runs inside one
`shard_map` with the part axis block-sharded across devices. Cross-part
traffic then rides the MeshRouter's fixed-capacity all_to_all instead of
the LocalRouter's flat scatter (`repro/dist/router.py`); the carry's
NamedShardings live in `repro/dist/sharding.py`. Both routers are
golden-equivalent by test.

Hybrid parallelism (ISSUE 7): a 2-D ("stage", "data") mesh
(`make_stream_mesh(stage=S)` + `PipelineConfig.n_stages=S`) additionally
pipelines the LAYER axis: layer l lives on stage l % S, each tick every
stage runs its R = L // S layers on data that is s ticks behind the
stream head, and inter-stage hops ride a packed ring in the carry
(`PipelineCarry.stage_ring`), posted with one circular `ppermute` right
after each round's compute so the hop overlaps the next round's work
(`_tick_program_2d`). Per-tick behaviour is schedule-skewed relative to
the 1-D program, but the quiescent state after `flush` is the same
fixed point (aggregator updates telescope; edge counts are
arrival-order-independent — golden-tested against the LocalRouter
reference and the static oracle). At `n_stages=1` NONE of this code is
reached: the 1-D program above runs byte-for-byte unchanged.

Delivery backend: `PipelineConfig.delivery_backend` picks how routed
records land in state — "xla" (reference scatters) or "pallas" (sorted
segment-reduce kernels, `core/delivery.py`). Both backends run the same
program under both routers, golden-equivalent by test.

Query plane: `PipelineConfig.query_cap > 0` puts a per-part pending
point-query table (`repro/serve/query.py:QueryState`) in the carry and
runs the query stage at the end of every tick, AFTER the sink update:
embedding reads and on-device link scores answered straight from the
live sharded state, with per-query freshness (`stale_ok` vs
`consistent`). Answers ride the super-tick scan as its ys — still ONE
host sync per super-tick (the stats read now also carries the answers),
taken at once by any launch that admits a query or runs while one is
held, so answers are never left pending.
Answered rows accumulate host-side; `drain_answers()` pops them
(`repro/serve/session.py:ServeSession` wraps this with latency
accounting). `query_cap=0` (default) statically compiles the plane away.

Training plane (ISSUE 8): pass `train=TrainConfig(...)` with
`PipelineConfig.train_cap > 0` and every tick ENDS with a windowed
online training step through the live sharded state
(`core/train_plane.py`): label events ride a per-tick `LabelBatch`,
the sliding-window batch (recently-touched labeled masters) fires a
fire-masked layered backprop + Algorithm 3 update whose two cross-part
gradient hops ride the same packed wire as the routing plane, and
`TrainState` (labels, live params, per-part optimizer state,
error-feedback residuals) lives in the donated carry — still ONE host
sync per super-tick, at once for a launch that admits labels;
`train_stats()` reads progress on demand.
`train_cap=0` (default) statically compiles the plane away:
the program is bit-for-bit the four-plane tick.
`serve/train_session.py:TrainSession` wraps the label queue/driver
loop, mirroring ServeSession.

Telemetry plane (ISSUE 9): `PipelineConfig.telemetry=True` turns on the
SIXTH plane — the one that watches the other five. On device, TickStats
grows exact occupancy gauges (defer-ring populations, pre-cap route and
per-part outbox demand peaks) and each tick emits one occupancy row
that rides the super-tick scan's ys — still ONE host sync, taken at
once by every launch while the plane is on. On the host,
every tick appends a row (device gauges + wall/staging timings + exact
wire bytes + ingest counts) to `telemetry/trace.py:TraceRecorder`
(`save_trace()` -> .npz) and feeds `ft/stragglers.py`; the capacity
advisor (`telemetry/advisor.py`) consumes the trace offline. `telemetry=False`
(default) keeps the gauges as static zeros — XLA dead-code-eliminates
them and the program is bit-for-bit the five-plane tick.

Staging model / constraints:
  - batch capacities derive from PipelineConfig, so every tick's batches
    have identical shapes and stack cleanly along T;
  - the streaming partitioner stays host-side and sequential: staging T
    ticks replays host partitioning for each tick up front, which is valid
    because partitioner state never depends on device results;
  - donation invalidates the previous device buffers — never hold
    references to `pipe.topo`/`pipe.states`/`pipe.sink` across a
    super-tick; re-read them from the pipeline object.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import events as ev
from repro.core import state as st
from repro.core import windowing as win
from repro.core.delivery import BACKENDS as DELIVERY_BACKENDS
from repro.core.delivery import make_delivery
from repro.core.explosion import layer_parallelisms, physical_busy
from repro.core.partitioner import StreamingPartitioner
from repro.core.tick import add_stats, layer_tick_body, zero_stats
from repro.core.termination import (TerminationCoordinator, moved_msgs,
                                    quiet_update)
from repro.dist.router import LocalRouter, MeshRouter
from repro.dist.sharding import (carry_pspecs, carry_shardings,
                                 stage_carry_pspecs, stage_carry_shardings,
                                 stage_stats_pspecs, stats_pspecs)
from repro.dist.wire import field_col, pack_lane, pad_lane, unpack_lane
from repro.ft.stragglers import StragglerMitigator
from repro.telemetry.spans import SpanClock
from repro.telemetry.trace import TRACE_DEVICE_COLS, TraceRecorder
from repro.core.train_plane import (TrainConfig, init_train_state,
                                    train_pspecs, train_shardings,
                                    train_stage)
from repro.serve.query import (KIND_LINK, add_query_stats,
                               empty_query_batch, init_query_state,
                               query_admit_stage, query_answer_stage,
                               query_batch_from_numpy, wire_width,
                               zero_query_stats)


@dataclass(frozen=True)
class Capacities:
    """Every RESOLVED per-tick budget of a (config, mesh) pair — the one
    documented view of the capacity arithmetic.  Read it once per launch
    site:

        caps = cfg.capacities(n_devices)

    Defer-ring rows are GLOBAL (n_devices * per-device) and 0 whenever
    the capped exchange cannot overflow (dense default, one device, or
    route_cap >= the lane capacity) — a zero compiles the backpressure
    path away (dist/wire.py)."""
    outbox: int            # per-tick emission budget (rows, all parts)
    outbox_per_part: int   # emission slots per part (outbox // n_parts)
    query_admissions: int  # query rows admitted per tick (0 = plane off)
    train_cap: int         # label rows admitted per tick (0 = plane off)
    bc_defer_rows: int     # broadcast-lane defer-ring rows
    rmi_defer_rows: int    # RMI-lane defer-ring rows
    query_defer_rows: int  # query-wire-lane defer-ring rows


@dataclass
class PipelineConfig:
    n_parts: int = 8                  # logical parts (= max_parallelism)
    node_cap: int = 512               # per-part vertex slots
    edge_cap: int = 2048              # per-part edge slots
    repl_cap: int = 1024              # per-part replication records
    feat_cap: int = 1024              # host-inbox feature rows per tick
    outbox_cap: Optional[int] = None  # per-tick emission budget (default:
                                      # feat_cap), split evenly over parts
    edge_tick_cap: int = 1024         # new-edge records per tick
    query_cap: int = 0                # per-part pending point-query slots
                                      # (0 = query plane compiled away)
    query_tick_cap: Optional[int] = None  # query admissions per tick
                                      # (default: query_cap * n_parts)
    train_cap: int = 0                # training plane (ISSUE 8): label
                                      # admissions per tick (0 = the plane
                                      # compiles away; > 0 needs a
                                      # TrainConfig passed as
                                      # D3Pipeline(train=...))
    route_cap: Optional[int] = None   # routing plane: per-destination
                                      # all_to_all bucket rows (None = each
                                      # lane's full capacity — dense,
                                      # never-overflow semantics); smaller
                                      # caps shrink the wire D x C -> D x
                                      # cap and defer overflow as
                                      # backpressure (dist/router.py)
    route_defer_cap: Optional[int] = None  # per-device defer-ring rows per
                                      # lane (default: the lane's local
                                      # capacity); only meaningful with
                                      # route_cap set on a multi-device
                                      # mesh
    window: win.WindowConfig = field(default_factory=win.WindowConfig)
    delta_eps: float = 0.0            # delta-gated propagation (ISSUE 6):
                                      # a touched vertex only re-emits when
                                      # ||phi(x) - phi(x_sent)|| > eps
                                      # (core/tick.py:round_b_emit). 0.0 =
                                      # exact mode, bit-for-bit the ungated
                                      # program; > 0 bounds the per-vertex
                                      # un-sent delta by eps (approximate,
                                      # error-bounded) and coalesces
                                      # same-destination RMIs pre-routing
    delivery_backend: str = "xla"     # how routed records land in state
                                      # ("xla" scatters | "pallas" kernels)
    n_stages: int = 1                 # hybrid parallelism (ISSUE 7): number
                                      # of pipeline stages on a 2-D
                                      # ("stage","data") mesh — layer l runs
                                      # on stage l % n_stages and micro-ticks
                                      # flow as a circular pipeline. Must
                                      # match make_stream_mesh(stage=...);
                                      # 1 (default) = the layer-sequential
                                      # 1-D program, bit-for-bit
    telemetry: bool = False           # telemetry plane (ISSUE 9): True
                                      # lights up exact per-plane occupancy
                                      # gauges (TickStats/RouteReceipt), a
                                      # per-tick occupancy row riding the
                                      # scan ys, and the host-side
                                      # TraceRecorder (D3Pipeline.trace) +
                                      # StragglerMitigator feed. False
                                      # (default) compiles every gauge to a
                                      # static zero — bit-for-bit the
                                      # untraced program
    partitioner: str = "hdrf"
    base_parallelism: int = 2         # p  (physical, for stats/sharding)
    explosion: float = 1.0            # lambda
    max_nodes: int = 100_000          # global id space for the host tables
    seed: int = 0

    # -------------------------------------------- resolved budget views
    def capacities(self, n_devices: int = 1) -> Capacities:
        """The one documented view of every resolved per-tick budget.

        n_devices is the DATA-axis device count (defer-ring rows are
        sized per data shard); 1 covers the LocalRouter and any
        single-data-shard mesh.  See `Capacities` for field semantics.
        """
        p_loc = self.n_parts // max(n_devices, 1)
        return Capacities(
            outbox=self._outbox(),
            outbox_per_part=max(1, self._outbox() // self.n_parts),
            query_admissions=self._query_admissions(),
            train_cap=self.train_cap,
            bc_defer_rows=self._defer_rows(p_loc * self.repl_cap,
                                           n_devices),
            rmi_defer_rows=self._defer_rows(
                self.edge_tick_cap + p_loc * self.edge_cap, n_devices),
            query_defer_rows=self._defer_rows(p_loc * self.query_cap,
                                              n_devices))

    def _outbox(self) -> int:
        return self.feat_cap if self.outbox_cap is None else self.outbox_cap

    def _query_admissions(self) -> int:
        if self.query_cap <= 0:
            return 0
        return (self.query_cap * self.n_parts if self.query_tick_cap is None
                else self.query_tick_cap)

    def _defer_rows(self, lane_capacity: int, n_devices: int) -> int:
        if n_devices <= 1 or self.route_cap is None:
            return 0
        if self.route_cap >= lane_capacity:    # bucket >= lane: no overflow
            return 0
        per_dev = (lane_capacity if self.route_defer_cap is None
                   else self.route_defer_cap)
        return n_devices * per_dev

    def validate(self, n_devices: int = 1, n_layers: Optional[int] = None,
                 local: bool = False) -> None:
        """Fail fast with a clear message instead of a shard_map shape
        error deep inside the tick program.

        n_devices counts the WHOLE mesh (stage * data on a 2-D mesh);
        n_layers enables the layer-placement divisibility check; local
        flags a LocalRouter pipeline (no mesh), which cannot host
        pipeline stages."""
        if self.n_stages < 1:
            raise ValueError(
                f"PipelineConfig.n_stages={self.n_stages} must be >= 1 "
                "(1 = the layer-sequential 1-D program)")
        if self.n_stages > 1:
            if local:
                raise ValueError(
                    f"PipelineConfig.n_stages={self.n_stages} needs a 2-D "
                    "('stage','data') mesh (make_stream_mesh(stage=...)): "
                    "the LocalRouter has no stage axis to place layers on "
                    "and would silently run them layer-sequentially — "
                    "pass mesh= or set n_stages=1")
            if n_devices % self.n_stages:
                raise ValueError(
                    f"n_devices={n_devices} is not divisible by "
                    f"n_stages={self.n_stages}: the mesh factors as "
                    "(stage, data) = (n_stages, n_devices // n_stages), "
                    "so pick a device count that is a multiple of the "
                    "stage count")
            if n_layers is not None and n_layers % self.n_stages:
                raise ValueError(
                    f"n_layers={n_layers} is not divisible by "
                    f"n_stages={self.n_stages}: layers are placed "
                    "round-robin on stages (layer l on stage l % S) and "
                    "every stage must carry the same number of rounds — "
                    "use a stage count that divides the layer count")
        caps = {"n_parts": self.n_parts, "node_cap": self.node_cap,
                "edge_cap": self.edge_cap, "repl_cap": self.repl_cap,
                "feat_cap": self.feat_cap,
                "outbox_cap (capacities().outbox)": self._outbox(),
                "edge_tick_cap": self.edge_tick_cap}
        for name, v in caps.items():
            if v <= 0:
                raise ValueError(f"PipelineConfig.{name}={v} must be > 0")
        if self.query_cap < 0:
            raise ValueError(f"PipelineConfig.query_cap={self.query_cap} "
                             "must be >= 0 (0 disables the query plane)")
        if self.query_cap == 0 and self.query_tick_cap:
            raise ValueError(
                "PipelineConfig.query_tick_cap is set but query_cap=0 — "
                "the query plane is disabled; set query_cap > 0 to serve")
        if self.query_cap > 0 and self._query_admissions() <= 0:
            raise ValueError(
                f"PipelineConfig.query_tick_cap={self.query_tick_cap} "
                "must be > 0 (capacities().query_admissions) when the "
                "query plane is enabled")
        if self.train_cap < 0:
            raise ValueError(
                f"PipelineConfig.train_cap={self.train_cap} must be >= 0 "
                "(0 disables the training plane; see "
                "capacities().train_cap)")
        if not (self.delta_eps >= 0.0):   # rejects negatives AND NaN
            raise ValueError(
                f"PipelineConfig.delta_eps={self.delta_eps} must be a "
                "finite value >= 0 (0 = exact/ungated propagation)")
        if self.route_cap is not None and self.route_cap <= 0:
            raise ValueError(
                f"PipelineConfig.route_cap={self.route_cap} must be > 0 "
                "(or None for the dense never-overflow exchange)")
        if self.route_defer_cap is not None and self.route_defer_cap < 0:
            raise ValueError(
                f"PipelineConfig.route_defer_cap={self.route_defer_cap} "
                "must be >= 0 (0 disables deferral: bucket overflow then "
                "drops, counted in TickStats.route_dropped)")
        # parts shard over the DATA axis only — on a 2-D mesh each stage
        # row replicates the same part blocks over n_devices // n_stages
        # data shards
        data_devs = n_devices // self.n_stages if self.n_stages > 1 \
            else n_devices
        if (self.route_defer_cap == 0 and self.query_cap > 0
                and self.route_cap is not None and data_devs > 1
                and self.route_cap < (self.n_parts // data_devs)
                * self.query_cap):
            raise ValueError(
                "route_defer_cap=0 with a capped query wire lane "
                f"(route_cap={self.route_cap} < per-device wire capacity "
                f"{(self.n_parts // n_devices) * self.query_cap}): a "
                "dropped link-tail record would strand its qid with no "
                "ok=False answer — MsgBatch lanes may drop loudly, the "
                "wire lane must be able to defer. Leave route_defer_cap "
                "unset (defaults to the lane capacity) or raise route_cap")
        if self.delivery_backend not in DELIVERY_BACKENDS:
            raise ValueError(
                f"PipelineConfig.delivery_backend="
                f"{self.delivery_backend!r} is not registered: pick one of "
                f"{sorted(DELIVERY_BACKENDS)} (core/delivery.py)")
        if self._outbox() % self.n_parts:
            raise ValueError(
                f"the emission budget capacities().outbox="
                f"{self._outbox()} (outbox_cap or feat_cap) must be a "
                f"multiple of n_parts={self.n_parts}: it is split into "
                "capacities().outbox_per_part emission slots per part")
        if data_devs > 1 and self.n_parts % data_devs:
            raise ValueError(
                f"n_parts={self.n_parts} is not divisible by the mesh's "
                f"{data_devs} devices: the part axis is block-sharded over "
                "('data',), so pick n_parts as a multiple of the device "
                "count (each device owns n_parts // n_devices parts)")


@dataclass
class StreamMetrics:
    ticks: int = 0
    emitted_total: int = 0
    reduce_msgs: int = 0
    broadcast_msgs: int = 0
    cross_part_msgs: int = 0
    dropped: int = 0
    queries_admitted: int = 0
    queries_answered: int = 0
    queries_dropped: int = 0
    query_hold_ticks: int = 0          # pending-query-ticks (backlog integral)
    # measured routing-plane wire telemetry (ISSUE 5): summed over every
    # all_to_all launch of every tick — what bench_comm_volume.py reports
    wire_rows: int = 0                 # live records shipped on the wire
    wire_bytes: int = 0                # exchanged send-buffer bytes
    route_deferred: int = 0            # records carried by backpressure
    route_dropped: int = 0             # records lost to FULL defer rings
                                       # (0 in any correctly-sized config)
    suppressed: int = 0                # delta-gated RMIs NOT emitted
                                       # (ISSUE 6; 0 at delta_eps=0) —
                                       # the saved message volume:
                                       # reduce_msgs + suppressed tracks
                                       # the ungated reduce_msgs
    # compacted delivery: lane rows the RMI deliveries covered (whole
    # deliver_chunk steps, summed over layers and devices) and the rows
    # the delivered lanes held (their static capacity);
    # 1 - deliver_rows / deliver_lane_rows is the share compaction saved
    deliver_rows: int = 0
    deliver_lane_rows: int = 0
    stage_idle: int = 0                # hybrid pipeline bubbles (ISSUE 7):
                                       # device-rounds that saw an EMPTY
                                       # inbox, summed over ticks — 0 on a
                                       # 1-D mesh; D3Pipeline.
                                       # bubble_fraction() normalizes it
    # telemetry plane (ISSUE 9) — all 0 unless PipelineConfig.telemetry:
    occ_defer_ticks: int = 0           # defer-ring backlog INTEGRAL
                                       # (end-of-tick bc+rmi ring rows,
                                       # summed over ticks — the
                                       # query_hold_ticks convention)
    route_peak: int = 0                # MAX per-tick per-dest bucket
                                       # demand pre-cap (the zero-defer
                                       # route_cap for the traffic seen)
    outbox_peak: int = 0               # MAX per-tick per-layer GLOBAL
                                       # emission demand (emitted+dropped)
    outbox_part_peak: int = 0          # MAX per-tick PER-PART eviction
                                       # demand — the cap binds per part,
                                       # so zero-drop needs outbox_cap >=
                                       # n_parts x outbox_part_peak
    busy_logical: Optional[np.ndarray] = None
    # host spans (telemetry/spans.py): {name: SpanStat(count, total_s,
    # self_s)} for d3.chunk, d3.launch, d3.stage, d3.stage.partition,
    # d3.stage.pack, d3.dispatch, d3.sync, d3.harvest and d3.drain
    spans: dict = field(default_factory=dict)
    # staging counters, one increment per staged launch
    edges_staged: int = 0              # edges handed to the partitioner
    feat_rows_staged: int = 0          # feature events handed to staging
    feat_slots_uploaded: int = 0       # padded feature slots (feat_cap x T)
    upload_bytes: int = 0              # nbytes of the batch leaves launched
    launches: int = 0                  # device launches (ticks or scans)
    drain_launches: int = 0            # of which flush launches
    launches_overlapped: int = 0       # super-tick launches whose sync
                                       # waited until the next launch was
                                       # staged and dispatched

    @property
    def host_seconds(self) -> float:
        """Host staging seconds: the d3.stage span's total."""
        st = self.spans.get("d3.stage")
        return st.total_s if st is not None else 0.0

    @property
    def wall_seconds(self) -> float:
        """Launch wall seconds: the d3.launch span's total."""
        st = self.spans.get("d3.launch")
        return st.total_s if st is not None else 0.0

    @property
    def throughput(self) -> float:
        return self.emitted_total / self.wall_seconds if self.wall_seconds else 0.0


@dataclass
class _Launch:
    """One dispatched super-tick launch: its host facts, and until the
    host reads them its device outputs (`D3Pipeline._dispatch_super`)."""
    ticks: int
    tick0: int
    counts: list                       # (edges, feats, queries, labels)
                                       # per tick
    span: object = None                # its d3.launch OpenSpan
    host_s: float = 0.0                # its staging seconds
    outs: tuple = None                 # unread device outputs
    result: tuple = None               # (per-layer stats, quiet) once read


class LaunchResult:
    """What `run_super_tick` returns: the pair (per-layer summed
    TickStats, quiet_ticks). Unpacking or indexing it reads the launch
    first if it is still pending; a caller that drops it never waits."""
    __slots__ = ("_pipe", "_launch")

    def __init__(self, pipe, launch: _Launch):
        self._pipe = pipe
        self._launch = launch

    def _value(self) -> tuple:
        if self._launch.result is None:
            self._pipe.settle()
        if self._launch.result is None:
            raise RuntimeError("the launch was never read: reading it "
                               "raised earlier")
        return self._launch.result

    def __iter__(self):
        return iter(self._value())

    def __getitem__(self, i):
        return self._value()[i]

    def __len__(self) -> int:
        return 2


@dataclass(frozen=True)
class StagedActLayer:
    """SPMD-uniform stand-in for one pipeline ROUND of layers.

    Under stage parallelism one compiled `layer_tick_body` runs for every
    stage of a round, but GraphSAGE stacks put `act=False` on the final
    layer only — the one per-layer difference that is CODE, not data. The
    wrapper moves it into data: `base` is the round's layer with act
    forced off, and the staged params carry {"p": the layer's params,
    "act": 0/1 float} stacked over the stage axis, so the relu rides a
    `jnp.where` on a per-stage leaf instead of a per-layer Python branch.
    Valid for any layer whose activation is exactly a final relu
    (SAGELayer / GCNLayer); D3Pipeline enforces the rest of the
    uniformity contract (same class / dims / aggregator across layers).
    """
    base: object

    @property
    def agg_kind(self):
        return getattr(self.base, "agg_kind", "mean")

    @property
    def in_dim(self):
        return self.base.in_dim

    @property
    def out_dim(self):
        return self.base.out_dim

    def message(self, params, x):
        return self.base.message(params["p"], x)

    def update(self, params, x, agg):
        h = self.base.update(params["p"], x, agg)
        return jnp.where(params["act"] > 0, jax.nn.relu(h), h)


def _fresh_tables(n_parts, node_cap, edge_cap, repl_cap, dims, n_stages,
                  n_rounds, bc_rows, rmi_rows, query_cap, query_defer_rows,
                  ring_rows):
    """A pipeline's zeroed device tables: (topology, layer states, sink,
    sink_seen, pending queries, inter-stage ring). On a 2-D mesh
    (n_stages > 1) each of the n_rounds states stacks its round's layers
    over a leading stage axis (all layers initialize identically) and the
    ring is [S, rounds, *ring_rows]; on one stage the ring is None."""
    topo = st.init_topo(n_parts, edge_cap, repl_cap, node_cap)
    if n_stages > 1:
        d = dims[0]
        proto = st.init_layer(n_parts, node_cap, d, d, bc_defer_rows=bc_rows,
                              rmi_defer_rows=rmi_rows)
        states = [jax.tree.map(lambda a: jnp.stack([a] * n_stages), proto)
                  for _ in range(n_rounds)]
    else:
        states = [st.init_layer(n_parts, node_cap, d, d,
                                bc_defer_rows=bc_rows,
                                rmi_defer_rows=rmi_rows)
                  for d in dims[:-1]]
    queries = init_query_state(n_parts, query_cap, dims[-1],
                               wire_defer_rows=query_defer_rows)
    ring = (jnp.zeros((n_stages, n_rounds) + ring_rows, jnp.float32)
            if n_stages > 1 else None)
    return (topo, states, jnp.zeros((n_parts, node_cap, dims[-1])),
            jnp.zeros((n_parts, node_cap), bool), queries, ring)


@lru_cache(maxsize=None)
def _fresh_tables_on(mesh, *sizes):
    """`_fresh_tables(*sizes)` jitted to build every table in its place on
    `mesh`: each device zeroes its own shards, so the whole carry never
    lands on one device on its way onto the mesh. A table with no element
    (a defer ring of the dense wire) is built replicated, as a launch
    hands it back (XLA returns an empty output replicated, whatever its
    spec): the first launch of a pipeline then runs the program every
    later launch runs. Kept per mesh and sizes, so that a pipeline made
    again at the same sizes compiles nothing."""
    build = partial(_fresh_tables, *sizes)
    n_stages, n_rounds = sizes[5], sizes[6]
    sh = (stage_carry_shardings(mesh, n_rounds) if n_stages > 1
          else carry_shardings(mesh, n_rounds))
    rep = NamedSharding(mesh, P())
    out = jax.tree.map(lambda s, a: rep if a.size == 0 else s,
                       (sh.topo, list(sh.layers), sh.sink, sh.sink_seen,
                        sh.queries, sh.stage_ring), jax.eval_shape(build))
    return jax.jit(build, out_shardings=out)


class D3Pipeline:
    """L chained GraphStorage operators + the host driver."""

    def __init__(self, model, params, cfg: PipelineConfig, mesh=None,
                 train: Optional[TrainConfig] = None):
        """model: graph/sage.GraphSAGE (or compatible stack of layers with
        .message/.update); params: its param pytree.
        mesh: optional jax mesh — 1-D ("data",) shards the part axis of
        the tick program across its devices (MeshRouter); 2-D ("stage",
        "data") with cfg.n_stages > 1 additionally pipelines the layer
        axis (`make_stream_mesh(stage=...)`).
        train: optional TrainConfig — enables the ONLINE training plane
        (cfg.train_cap > 0 required): every tick ends with a windowed
        training step over the live sharded state
        (core/train_plane.py)."""
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        mesh_shape = dict(mesh.shape) if mesh is not None else {}
        S = int(mesh_shape.get("stage", 1))
        n_dev = int(mesh_shape.get("data", 1))
        if mesh is not None and S != cfg.n_stages:
            raise ValueError(
                f"mesh has stage={S} but PipelineConfig.n_stages="
                f"{cfg.n_stages}: the stage counts must agree — build the "
                "mesh with make_stream_mesh(stage=n_stages)")
        cfg.validate(n_devices=S * n_dev, n_layers=len(model.layers),
                     local=mesh is None)
        if (train is not None) != (cfg.train_cap > 0):
            raise ValueError(
                f"train={'set' if train is not None else 'None'} but "
                f"PipelineConfig.train_cap={cfg.train_cap}: the online "
                "training plane needs BOTH a TrainConfig (the knobs) and "
                "train_cap > 0 (the per-tick label admission budget, "
                "capacities().train_cap) — set both or neither")
        if train is not None and "head" not in params:
            raise ValueError(
                "train= needs an output operator: build the model with "
                "n_classes > 0 (GraphSAGE(dims, n_classes=...)) so its "
                "params carry a 'head' entry to train")
        self.train_cfg = train
        self._head = getattr(model, "head", None) if train is not None \
            else None
        self.n_stages = S
        self._n_data = n_dev
        self.router = (MeshRouter(cfg.n_parts, n_dev,
                                  route_cap=cfg.route_cap,
                                  pack_backend=cfg.delivery_backend,
                                  stage_axis="stage" if S > 1 else None,
                                  n_stages=S, telemetry=cfg.telemetry)
                       if mesh is not None else LocalRouter(cfg.n_parts))
        self.delivery = make_delivery(cfg.delivery_backend)
        self.layers = list(model.layers)
        self.params = params
        self.part = StreamingPartitioner(
            cfg.n_parts, cfg.max_nodes, method=cfg.partitioner,
            seed=cfg.seed, node_cap=cfg.node_cap, edge_cap=cfg.edge_cap,
            repl_cap=cfg.repl_cap)
        dims = [l.in_dim for l in self.layers] + [self.layers[-1].out_dim]
        # every resolved per-tick budget, incl. the routing-plane
        # backpressure rings sized per lane from the LOCAL (per-device)
        # emission capacities (0 rows = compiled away)
        caps = cfg.capacities(n_dev)
        p_loc = cfg.n_parts // n_dev
        bc_rows = caps.bc_defer_rows
        rmi_rows = caps.rmi_defer_rows
        if S > 1:
            self._check_uniform_layers(dims)
            self._n_rounds = len(self.layers) // S
            self.rounds = (StagedActLayer(
                base=replace(self.layers[0], act=False)),) * self._n_rounds
        else:
            self._n_rounds = len(self.layers)
            self.rounds = None
        self.d_out = dims[-1]
        # inter-stage ring: one fixed packed-FeatBatch slot shape carries
        # both the host inbox (feat_cap rows) and any round's outbox
        # (p_loc * cap_pp rows) between stages
        cap_pp = caps.outbox_per_part
        self._ring_caps = (max(cfg.feat_cap, p_loc * cap_pp), dims[0] + 3)

        sizes = (cfg.n_parts, cfg.node_cap, cfg.edge_cap, cfg.repl_cap,
                 tuple(dims), S, self._n_rounds, bc_rows, rmi_rows,
                 cfg.query_cap, caps.query_defer_rows,
                 (n_dev * self._ring_caps[0], self._ring_caps[1]))
        tables = (_fresh_tables(*sizes) if mesh is None
                  else _fresh_tables_on(mesh, *sizes)())
        (self.topo, self.states, self.sink, self.sink_seen, self.queries,
         self.stage_ring) = tables
        # the training plane's device state: labels/dirty window, live
        # params, per-part optimizer state (core/train_plane.py)
        self.train_state = (init_train_state(
            cfg.n_parts, cfg.node_cap,
            {f"l{i}": params[f"l{i}"] for i in range(len(self.layers))},
            params["head"], train) if train is not None else None)
        self._acts = tuple(
            1.0 if getattr(l, "act", False) else 0.0 for l in self.layers)
        self._wire_bytes_per_tick = self._static_wire_bytes(dims, n_dev, S)
        self._lane_rows_per_tick = self._static_lane_rows(n_dev)
        if mesh is not None and self.train_state is not None:
            self.train_state = jax.device_put(
                self.train_state, train_shardings(mesh, self.train_state))
        self.now = 0
        self._metrics = StreamMetrics(
            busy_logical=np.zeros(cfg.n_parts, np.int64))
        self._clock = SpanClock(self._metrics.spans)
        # the one dispatched super-tick whose outputs are not read yet
        # (`run_super_tick`), and whether the query plane's table held a
        # query at the end of the last launch the host read
        self._pending: Optional[_Launch] = None
        self._queries_held = False
        # host-resident empty batches for staging (stacked before upload)
        empty_rows = {k: np.zeros(0, np.int64) for k in
                      ("part", "edge_slot", "src_slot", "dst_slot",
                       "dst_master_part", "dst_master_slot")}
        self._empty_edges = ev.edge_batch_from_numpy(empty_rows,
                                                     cfg.edge_tick_cap)
        self._empty_queries = empty_query_batch(caps.query_admissions,
                                                self.d_out)
        z0 = np.zeros(0, np.int64)
        self._empty_labels = ev.label_batch_from_numpy(z0, z0, z0,
                                                       cfg.train_cap)
        self._answer_log: list = []    # host-side answered-row columns
        # telemetry plane: the trace recorder + straggler feed
        if cfg.telemetry:
            from dataclasses import asdict
            self.trace = TraceRecorder(meta={
                "n_parts": cfg.n_parts, "n_devices": n_dev, "n_stages": S,
                "n_layers": len(self.layers), "dims": list(dims),
                "window": cfg.window.kind,
                "delivery_backend": cfg.delivery_backend,
                "delta_eps": cfg.delta_eps,
                "route_cap": cfg.route_cap,
                "route_defer_cap": cfg.route_defer_cap,
                "node_cap": cfg.node_cap, "edge_cap": cfg.edge_cap,
                "repl_cap": cfg.repl_cap, "feat_cap": cfg.feat_cap,
                "edge_tick_cap": cfg.edge_tick_cap,
                "query_cap": cfg.query_cap,
                "query_tick_cap": cfg.query_tick_cap,
                "train_cap": cfg.train_cap,
                "caps": asdict(caps),
                "wire_bytes_per_tick": self._wire_bytes_per_tick})
            self.straggler = StragglerMitigator(n_shards=max(n_dev, 1))
        else:
            self.trace = None
            self.straggler = None

    @property
    def metrics(self) -> StreamMetrics:
        """The stream's counters, with the pending launch settled first."""
        self.settle()
        return self._metrics

    def save_trace(self, path) -> None:
        """Write the recorded telemetry trace (needs cfg.telemetry)."""
        assert self.trace is not None, \
            "telemetry plane disabled (PipelineConfig.telemetry=False)"
        self.settle()
        self.trace.save(path)

    def parts_per_shard(self) -> list:
        """Logical parts owned by each data shard (block sharding) — the
        StragglerMitigator's work-steal planner input."""
        D = max(self._n_data, 1)
        p_loc = self.cfg.n_parts // D
        return [np.arange(d * p_loc, (d + 1) * p_loc) for d in range(D)]

    def _static_wire_bytes(self, dims, n_dev: int, n_stages: int = 1) -> int:
        """EXACT collective bytes per tick across the whole mesh — a
        compile-time constant of (config, mesh): every device ships a
        [D, cap * W] f32 send buffer per lane per route_lanes call, so
        per-tick bytes = D * sum_lanes D * cap * W * 4. Accounted here in
        host int arithmetic (StreamMetrics.wire_bytes) instead of on
        device, where a float counter would round past 2**24 and an
        int32 one would overflow at production capacities. The lane
        capacities/widths are the same constants the defer-ring sizing
        above uses (MsgBatch width d + 5, QueryBatch width d + 10).

        On a 2-D mesh the data-axis exchange happens once per ROUND per
        stage row (each stage runs R = L // S layers), the query wire
        rides round 0 on EVERY stage (QueryState is stage-replicated),
        and the stage axis adds its own wires: one [C_buf, W_fb] ppermute
        per round per device plus the final-round all_gather feeding the
        replicated sinks (S - 1 foreign slots per device).

        The TRAINING plane (cfg.train_cap > 0) adds two DENSE lanes per
        layer per tick (hop A: repl_cap rows of dagg; hop B: node_cap
        rows of source gradients — always full capacity, route_cap does
        not apply to gradient lanes) and, on a 2-D mesh, the per-round
        stage all_gather of the layer caches (feat/agg/agg_cnt) every
        stage's backward reads."""
        if self.mesh is None:
            return 0
        cfg = self.cfg
        p_loc = cfg.n_parts // n_dev
        if n_stages > 1:
            lanes = []
            for _ in range(self._n_rounds):
                lanes.append((p_loc * cfg.repl_cap, dims[0] + 5))
                lanes.append((cfg.edge_tick_cap + p_loc * cfg.edge_cap,
                              dims[0] + 5))
            if cfg.query_cap > 0:
                lanes.append((p_loc * cfg.query_cap,
                              wire_width(self.d_out)))
            a2a = (n_stages * n_dev
                   * sum(n_dev * self.router.lane_cap(c) * w * 4
                         for c, w in lanes) if n_dev > 1 else 0)
            C_buf, W_fb = self._ring_caps
            slot = C_buf * W_fb * 4
            ring = n_stages * n_dev * self._n_rounds * slot
            gather = n_stages * n_dev * (n_stages - 1) * slot
            train = 0
            if self.train_cfg is not None:
                d = dims[0]
                if n_dev > 1:
                    train += (n_stages * n_dev * len(self.layers)
                              * n_dev * (p_loc * cfg.repl_cap
                                         + p_loc * cfg.node_cap)
                              * (d + 5) * 4)
                train += (n_stages * n_dev * (n_stages - 1)
                          * self._n_rounds
                          * p_loc * cfg.node_cap * (2 * d + 1) * 4)
            return a2a + ring + gather + train
        if n_dev <= 1:
            return 0
        lanes = []
        for li in range(len(self.layers)):
            lanes.append((p_loc * cfg.repl_cap, dims[li] + 5))
            lanes.append((cfg.edge_tick_cap + p_loc * cfg.edge_cap,
                          dims[li] + 5))
        if cfg.query_cap > 0:
            lanes.append((p_loc * cfg.query_cap, wire_width(self.d_out)))
        total = n_dev * sum(n_dev * self.router.lane_cap(c) * w * 4
                            for c, w in lanes)
        if self.train_cfg is not None:
            total += n_dev * sum(
                n_dev * (p_loc * cfg.repl_cap + p_loc * cfg.node_cap)
                * (dims[li] + 5) * 4 for li in range(len(self.layers)))
        return total

    def _static_lane_rows(self, n_dev: int) -> int:
        """Rows of the delivered RMI lanes per tick, over every layer and
        device (StreamMetrics.deliver_lane_rows): each device receives,
        per layer, its whole emission lane on one data shard, else one
        `lane_cap` bucket from each of the n_dev shards."""
        cfg = self.cfg
        rows = cfg.edge_tick_cap + cfg.n_parts // n_dev * cfg.edge_cap
        if self.mesh is not None and n_dev > 1:
            rows = n_dev * self.router.lane_cap(rows)
        return len(self.layers) * n_dev * rows

    def _check_uniform_layers(self, dims) -> None:
        """Stage parallelism runs ONE compiled round body for every layer
        of a round, so the stack must be SPMD-uniform: same layer class,
        same aggregator, and in_dim == out_dim == d for every layer (one
        stacked state tree + one ring row width serve all rounds). The
        activation flag is exempt — StagedActLayer turns it into data."""
        base = self.layers[0]
        uniform = (len(set(dims)) == 1 and all(
            type(l) is type(base) and hasattr(l, "act")
            and getattr(l, "agg_kind", "mean")
            == getattr(base, "agg_kind", "mean")
            for l in self.layers))
        if not uniform:
            raise ValueError(
                f"PipelineConfig.n_stages={self.cfg.n_stages} needs an "
                "SPMD-uniform layer stack (same class/aggregator, in_dim "
                "== out_dim on every layer, differing at most in the "
                f"activation flag), got dims={dims} over "
                f"{[type(l).__name__ for l in self.layers]} — pipeline "
                "stages run one shared round body per stage")

    # ----------------------------------------------- hybrid-parallel host
    def _staged_params(self):
        """Per-round staged params for the pipelined program: round r's
        entry stacks layers r*S+0 .. r*S+S-1's params over a leading
        stage axis, plus the per-stage activation flag as a 0/1 float
        leaf (StagedActLayer). Rebuilt per launch from `self.params` so
        checkpoint restores of `params` need no extra bookkeeping."""
        S = self.n_stages
        out = {}
        for r in range(self._n_rounds):
            per = [self.params[f"l{r * S + s}"] for s in range(S)]
            out[f"r{r}"] = {
                "p": jax.tree.map(lambda *xs: jnp.stack(xs), *per),
                "act": jnp.asarray(
                    [1.0 if self.layers[r * S + s].act else 0.0
                     for s in range(S)], jnp.float32)}
        return out

    def _unstack_stats(self, host_stats):
        """Per-ROUND stacked stats ([S] scalars / [S, n_parts] busy) ->
        the 1-D program's per-LAYER list: layer l = r*S + s sits at index
        s of round r's stack."""
        out = []
        for l in range(len(self.layers)):
            r, s = divmod(l, self.n_stages)
            out.append(jax.tree.map(lambda a: a[s], host_stats[r]))
        return out

    def layer_state(self, l: int):
        """Host view of layer l's LayerState regardless of mesh shape: the
        1-D engine stores one state per layer; the hybrid engine stores one
        stage-STACKED state per round, with layer l = r*S + s living at
        stage index s of round r."""
        if self.n_stages == 1:
            return self.states[l]
        r, s = divmod(l, self.n_stages)
        return jax.tree.map(lambda a: a[s], self.states[r])

    def set_layer_state(self, l: int, st) -> None:
        """Write a per-layer LayerState back (inverse of layer_state) —
        used by the training coordinator's phased rebuild."""
        if self.n_stages == 1:
            self.states[l] = st
            return
        r, s = divmod(l, self.n_stages)
        self.states[r] = jax.tree.map(
            lambda a, leaf: a.at[s].set(leaf), self.states[r], st)

    def _ring_occupancy_host(self) -> int:
        """Valid rows still in flight between stages (0 on a 1-D mesh) —
        a reshard that changes the stage count must not drop them."""
        if self.stage_ring is None:
            return 0
        return int(jnp.sum(self.stage_ring[..., -1] > 0.5))

    def bubble_fraction(self) -> float:
        """Measured pipeline-bubble fraction: device-rounds that saw an
        empty inbox over total device-rounds (0.0 on a 1-D mesh)."""
        total = self.metrics.ticks * len(self.layers) * self._n_data
        if self.n_stages <= 1 or total == 0:
            return 0.0
        return self.metrics.stage_idle / total

    # --------------------------------------------- live elastic resharding
    def reshard(self, new_mesh, cfg: Optional[PipelineConfig] = None):
        """LIVE Alg. 5 elastic reshard (ISSUE 10): relay the whole carry —
        layer tables, defer rings, the inter-stage ring, QueryState,
        TrainState + optimizer state — from the current mesh onto
        `new_mesh` (another D-shard or S'xD' grid, or None for the
        LocalRouter) without dropping in-flight work.

        State arrays are keyed by LOGICAL part (fixed at n_parts), so the
        [P, ...] tables relayout with one `jax.device_put` onto the new
        shardings — no host round-trip per array, no graph
        re-partitioning. Only the three packed row buffers whose LAYOUT
        depends on the device count need re-blocking (ft/elastic.py):
        defer rings compact into the new global capacity (rows are
        destination-addressed — the router recomputes dst = part // p_loc
        at exchange time), and inter-stage ring slabs re-block by part
        ownership under the new p_loc (delivery drops rows outside the
        owner's block). Held `consistent` queries ride the QueryState
        tables and answer after the move exactly as without it.

        `cfg` optionally replaces the config (defaults to the current one
        with n_stages matched to the new mesh); it is validated against
        the new grid and installed — the PREVIOUS config object is never
        mutated. A stage-count change requires an empty inter-stage ring
        (flush() first); a reshard that would overflow the new defer
        capacities raises instead of silently dropping rows. Returns the
        installed config."""
        from repro.ft.elastic import repack_defer_ring, repack_stage_slab

        self.settle()
        L = len(self.layers)
        mesh_shape = dict(new_mesh.shape) if new_mesh is not None else {}
        S = int(mesh_shape.get("stage", 1))
        n_dev = int(mesh_shape.get("data", 1))
        if cfg is None:
            cfg = replace(self.cfg, n_stages=S)
        if new_mesh is not None and S != cfg.n_stages:
            raise ValueError(
                f"new mesh has stage={S} but cfg.n_stages={cfg.n_stages}: "
                "the stage counts must agree")
        cfg.validate(n_devices=S * n_dev, n_layers=L,
                     local=new_mesh is None)
        if (self.train_state is not None) != (cfg.train_cap > 0):
            raise ValueError(
                "reshard cannot turn the training plane on or off: "
                f"train_state is {'set' if self.train_state is not None else 'None'} "
                f"but cfg.train_cap={cfg.train_cap}")
        dims = [l.in_dim for l in self.layers] + [self.layers[-1].out_dim]
        caps = cfg.capacities(n_dev)
        p_loc = cfg.n_parts // n_dev
        old_S = self.n_stages

        def _lost(n, what):
            if int(n):
                raise RuntimeError(
                    f"reshard would drop {int(n)} in-flight {what} rows — "
                    "flush() to quiescence first or raise route_defer_cap")

        # per-LAYER view of the carry (unstacks the hybrid rounds); defer
        # rings compact into the new global capacities
        layer_states = [self.layer_state(l) for l in range(L)]
        for i, ls in enumerate(layer_states):
            b, bok, lb = repack_defer_ring(ls.bc_defer, ls.bc_defer_ok,
                                           caps.bc_defer_rows)
            r, rok, lr = repack_defer_ring(ls.rmi_defer, ls.rmi_defer_ok,
                                           caps.rmi_defer_rows)
            _lost(lb, f"layer {i} broadcast-defer")
            _lost(lr, f"layer {i} RMI-defer")
            layer_states[i] = replace(ls, bc_defer=b, bc_defer_ok=bok,
                                      rmi_defer=r, rmi_defer_ok=rok)
        qw, qok, lq = repack_defer_ring(self.queries.wire_defer,
                                        self.queries.wire_defer_ok,
                                        caps.query_defer_rows)
        _lost(lq, "query-wire-defer")
        queries = replace(self.queries, wire_defer=qw, wire_defer_ok=qok)

        # inter-stage ring: a stage-count change cannot relabel in-flight
        # rows' (stage, round) coordinates, so it needs an empty ring; a
        # data-axis-only reshard re-blocks rows by part ownership
        cap_pp = caps.outbox_per_part
        ring_caps = (max(cfg.feat_cap, p_loc * cap_pp), dims[0] + 3)
        in_flight = self._ring_occupancy_host()
        if S != old_S and in_flight:
            raise RuntimeError(
                f"reshard {old_S}->{S} stages with {in_flight} rows in the "
                "inter-stage ring — flush() to quiescence first "
                "(data-axis-only reshards keep in-flight rows)")
        new_ring = None
        if S > 1:
            if old_S == 1:
                self._check_uniform_layers(dims)
            n_rounds = L // S
            new_ring = jnp.zeros((S, n_rounds, n_dev * ring_caps[0],
                                  ring_caps[1]), jnp.float32)
            if old_S == S and self.stage_ring is not None:
                proto = ev.empty_feat_batch(1, dims[0])
                pcol = field_col(proto, "part")
                vcol = field_col(proto, "valid")
                slabs = []
                for s_i in range(S):
                    per_round = []
                    for r_i in range(self._n_rounds):
                        slab, lost = repack_stage_slab(
                            self.stage_ring[s_i, r_i], pcol, vcol,
                            p_loc, n_dev, ring_caps[0])
                        _lost(lost, f"stage-ring ({s_i},{r_i})")
                        per_round.append(slab)
                    slabs.append(jnp.stack(per_round))
                new_ring = jnp.stack(slabs)
            states = [jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[layer_states[r * S + s]
                                     for s in range(S)])
                      for r in range(n_rounds)]
            rounds = (StagedActLayer(
                base=replace(self.layers[0], act=False)),) * n_rounds
        else:
            n_rounds = L
            states = layer_states
            rounds = None

        # install the new grid: router, bookkeeping, device placement
        self.mesh = new_mesh
        self.cfg = cfg
        self.n_stages = S
        self._n_data = n_dev
        self._n_rounds = n_rounds
        self.rounds = rounds
        self.router = (MeshRouter(cfg.n_parts, n_dev,
                                  route_cap=cfg.route_cap,
                                  pack_backend=cfg.delivery_backend,
                                  stage_axis="stage" if S > 1 else None,
                                  n_stages=S, telemetry=cfg.telemetry)
                       if new_mesh is not None else LocalRouter(cfg.n_parts))
        self._ring_caps = ring_caps
        self._wire_bytes_per_tick = self._static_wire_bytes(dims, n_dev, S)
        self._lane_rows_per_tick = self._static_lane_rows(n_dev)
        if new_mesh is not None and S > 1:
            sh = stage_carry_shardings(new_mesh, n_rounds)
            self.topo = jax.device_put(self.topo, sh.topo)
            self.states = [jax.device_put(s, sh.layers[i])
                           for i, s in enumerate(states)]
            self.sink = jax.device_put(self.sink, sh.sink)
            self.sink_seen = jax.device_put(self.sink_seen, sh.sink_seen)
            self.queries = jax.device_put(queries, sh.queries)
            self.stage_ring = jax.device_put(new_ring, sh.stage_ring)
        elif new_mesh is not None:
            sh = carry_shardings(new_mesh, L)
            self.topo = jax.device_put(self.topo, sh.topo)
            self.states = [jax.device_put(s, sh.layers[i])
                           for i, s in enumerate(states)]
            self.sink = jax.device_put(self.sink, sh.sink)
            self.sink_seen = jax.device_put(self.sink_seen, sh.sink_seen)
            self.queries = jax.device_put(queries, sh.queries)
            self.stage_ring = None
        else:
            dev = jax.devices()[0]
            self.topo = jax.device_put(self.topo, dev)
            self.states = [jax.device_put(s, dev) for s in states]
            self.sink = jax.device_put(self.sink, dev)
            self.sink_seen = jax.device_put(self.sink_seen, dev)
            self.queries = jax.device_put(queries, dev)
            self.stage_ring = None
        if self.train_state is not None:
            self.train_state = (
                jax.device_put(self.train_state,
                               train_shardings(new_mesh, self.train_state))
                if new_mesh is not None
                else jax.device_put(self.train_state, jax.devices()[0]))
        if cfg.telemetry:
            if self.trace is not None:
                self.trace.meta["n_devices"] = n_dev
                self.trace.meta["n_stages"] = S
                self.trace.meta.setdefault("reshards", []).append(
                    {"tick": int(self.now), "n_devices": n_dev,
                     "n_stages": S})
            self.straggler = StragglerMitigator(n_shards=max(n_dev, 1))
        return cfg

    def mitigate_stragglers(self):
        """Consume the StragglerMitigator's persistent-straggler flags
        (fed live by the telemetry plane) end-to-end: a shard that stays
        flagged past `patience` is treated as fail-slow == fail-stop and
        the pipeline LIVE-reshards onto fewer data shards, re-mapping
        `parts_per_shard()` so the slow shard owns nothing. Returns the
        RescalePlan when a reshard happened, else None.

        Block sharding keeps parts contiguous, so the survivor count is
        the largest divisor of n_parts below the current D that also
        keeps the stage grid intact — work-steal overrides
        (`plan_work_steal`) stay the planner's advisory view; the reshard
        is the executable re-map."""
        from repro.ft.elastic import rescale_parts
        if self.straggler is None or self.mesh is None or self._n_data <= 1:
            return None
        slow = self.straggler.persistent_stragglers()
        if not slow:
            return None
        old_d = self._n_data
        new_d = old_d - len(set(slow))
        while new_d > 1 and self.cfg.n_parts % new_d:
            new_d -= 1
        new_d = max(new_d, 1)
        from repro.launch.mesh import survivor_mesh
        new_mesh = survivor_mesh(self.mesh, slow, n_data=new_d)
        plan = rescale_parts(old_d, new_d, self.cfg.n_parts)
        self.reshard(new_mesh)
        return plan

    # ------------------------------------------------------------ host side
    def _resolve_queries(self, queries, issue_tick: int) -> dict:
        """Resolve host query requests [(qid, kind, vid, [vid2], consistent)]
        to master-(part, slot)-addressed rows. Requests naming a vertex the
        partitioner has never seen are answered HERE (ok=False, zero
        payload, answer tick = issue tick) instead of burning device slots.
        """
        rows = {k: [] for k in ("qid", "kind", "part", "slot", "part2",
                                "slot2", "consistent", "issue")}
        rejects = []

        def locate(vid):
            if not 0 <= vid < self.cfg.max_nodes:
                return None
            return self.part.locate_master(vid, create=False)

        for q in queries:
            qid, kind, vid = int(q[0]), int(q[1]), int(q[2])
            vid2 = int(q[3]) if kind == KIND_LINK else 0
            # qids ride the packed f32 wire (dist/wire.py): values at or
            # beyond 2**24 would round and answer under the WRONG qid —
            # reject here, where the answer still carries the exact qid
            if not 0 <= qid < 2 ** 24:
                rejects.append((qid, kind))
                continue
            m = locate(vid)
            m2 = locate(vid2) if kind == KIND_LINK else (0, 0)
            if m is None or m2 is None:
                rejects.append((qid, kind))
                continue
            rows["qid"].append(qid)
            rows["kind"].append(kind)
            rows["part"].append(m[0])
            rows["slot"].append(m[1])
            rows["part2"].append(m2[0])
            rows["slot2"].append(m2[1])
            rows["consistent"].append(bool(q[-1]))
            rows["issue"].append(issue_tick)
        if rejects:
            r = np.asarray(rejects, np.int64).reshape(-1, 2)
            self._answer_log.append({
                "qid": r[:, 0], "kind": r[:, 1],
                "ok": np.zeros(len(r), bool),
                "tick": np.full(len(r), issue_tick, np.int64),
                "issue": np.full(len(r), issue_tick, np.int64),
                "vec": np.zeros((len(r), self.d_out), np.float32),
                "score": np.zeros(len(r), np.float32)})
        return {k: np.asarray(v) for k, v in rows.items()}

    def _build_batches(self, edges: Optional[np.ndarray],
                       feats: Optional[list], queries: Optional[list],
                       issue_tick: int, labels: Optional[list]):
        """One tick's padded batches, as numpy leaves: staging stacks a
        launch's ticks first and uploads each field once.
        labels: [(vid, gold_class), ...] training-plane admissions —
        resolved to master coordinates; vids the partitioner has never
        seen are silently skipped (no master slot to label)."""
        with self.span("d3.stage.partition"):
            rows = self._partition(edges, feats)
        with self.span("d3.stage.pack"):
            return self._pack(*rows, queries, issue_tick, labels)

    def _partition(self, edges, feats):
        """The partitioner's share of staging: place the tick's edges
        (HDRF), give every feature event its master slot (cold features
        create vertices), and drain the replica and vertex allocations."""
        if edges is not None and len(edges):
            e_rows, r1, v1 = self.part.ingest_edges(edges)
        else:
            e_rows, r1, v1 = None, None, None
        f_parts, f_slots, f_vecs = [], [], []
        if feats:
            coalesced = {}
            for vid, vec in feats:        # host-side coalescing (last wins)
                coalesced[int(vid)] = vec
            for vid, vec in coalesced.items():
                p, s = self.part.locate_master(vid)
                f_parts.append(p)
                f_slots.append(s)
                f_vecs.append(vec)
        r2, v2 = self.part.drain_allocations()
        if r1 is not None:
            r_rows = {k: np.concatenate([r1[k], r2[k]]) for k in r2}
            v_rows = {k: np.concatenate([v1[k], v2[k]]) for k in v2}
        else:
            r_rows, v_rows = r2, v2
        return e_rows, r_rows, v_rows, (f_parts, f_slots, f_vecs)

    def _pack(self, e_rows, r_rows, v_rows, f_rows, queries, issue_tick,
              labels):
        """Pad the partitioned rows into the tick's fixed-capacity
        batches (queries and labels resolved to master slots here)."""
        cfg = self.cfg
        f_parts, f_slots, f_vecs = f_rows
        eb = (ev.edge_batch_from_numpy(e_rows, cfg.edge_tick_cap)
              if e_rows is not None else self._empty_edges)
        rb = ev.repl_batch_from_numpy(r_rows, max(2 * cfg.edge_tick_cap, 1))
        vb = ev.vertex_batch_from_numpy(v_rows, max(2 * cfg.edge_tick_cap +
                                                    cfg.feat_cap, 1))
        fb = ev.feat_batch_from_numpy(
            np.asarray(f_parts), np.asarray(f_slots),
            np.asarray(f_vecs, np.float32).reshape(len(f_parts), -1)
            if f_parts else np.zeros((0, 1)),
            cfg.feat_cap, self.states[0].feat.shape[-1])
        if queries:
            assert cfg.query_cap > 0, \
                "queries submitted but PipelineConfig.query_cap=0"
            q_rows = self._resolve_queries(queries, issue_tick)
            qb = query_batch_from_numpy(q_rows, cfg._query_admissions(),
                                        self.d_out)
        else:
            qb = self._empty_queries
        if labels:
            assert cfg.train_cap > 0, \
                "labels submitted but PipelineConfig.train_cap=0"
            l_parts, l_slots, l_gold = [], [], []
            for vid, y in labels:
                m = self.part.locate_master(int(vid), create=False)
                if m is None:
                    continue
                l_parts.append(m[0])
                l_slots.append(m[1])
                l_gold.append(int(y))
            lb = ev.label_batch_from_numpy(
                np.asarray(l_parts, np.int64), np.asarray(l_slots, np.int64),
                np.asarray(l_gold, np.int64), cfg.train_cap)
        else:
            lb = self._empty_labels
        return eb, rb, vb, fb, qb, lb

    # ---------------------------------------------------------- device side
    def tick(self, edges: Optional[np.ndarray] = None,
             feats: Optional[list] = None, window=None,
             queries: Optional[list] = None,
             labels: Optional[list] = None):
        """One micro-tick through the full pipeline: a one-tick launch of
        `run_super_tick`, read at once. Returns its per-layer TickStats.

        queries: optional [(qid, kind, vid, [vid2,] consistent), ...]
        point-query admissions for this tick (needs cfg.query_cap > 0);
        answered rows accumulate in `drain_answers()`.
        labels: optional [(vid, gold_class), ...] training-plane label
        admissions for this tick (needs cfg.train_cap > 0 and a
        TrainConfig); training progress is read via `train_stats()`.
        """
        return list(self.run_super_tick(
            [edges], [feats], T=1, window=window, query_chunks=[queries],
            label_chunks=[labels])[0])

    def _sync_params_from_train(self) -> None:
        """Mirror the live trained parameters back into `self.params` so
        host-side consumers (checkpointing, `_staged_params`, the legacy
        coordinator) always see the online plane's latest step."""
        ts = self.train_state
        if ts is None:
            return
        for k, v in ts.params.items():
            self.params[k] = v
        self.params["head"] = ts.head_params

    def train_stats(self) -> dict:
        """Training-plane progress in ONE host sync: the last fired
        step's global loss, gradient norm and the fired-step count."""
        self.settle()
        ts = self.train_state
        assert ts is not None, \
            "training plane disabled (train_cap=0 / no TrainConfig)"
        loss, gn, steps = jax.device_get((ts.loss, ts.grad_norm, ts.steps))
        return {"loss": float(loss), "grad_norm": float(gn),
                "steps": int(steps)}

    def _harvest_answers(self, answers) -> None:
        """Pull this launch's answered rows (valid mask) into the host-side
        answer log. `answers` leaves are [T, A, ...] (the scan's ys);
        zero-capacity leaves mean the query plane is off."""
        if answers.valid.size == 0:
            return
        a = jax.device_get(answers)
        mask = np.asarray(a.valid).reshape(-1)
        if not mask.any():
            return
        flat = lambda x: np.asarray(x).reshape(-1)[mask]
        self._answer_log.append({
            "qid": flat(a.qid), "kind": flat(a.kind), "ok": flat(a.ok),
            "tick": flat(a.tick), "issue": flat(a.issue),
            "vec": np.asarray(a.vec).reshape(-1, a.vec.shape[-1])[mask],
            "score": flat(a.score)})

    def drain_answers(self) -> dict:
        """Pop every answered query collected so far as one dict of
        concatenated numpy columns (qid, kind, ok, tick, issue, vec,
        score) — empty arrays when nothing answered. A pending launch
        carries no answers (`run_super_tick`), so nothing is settled."""
        log, self._answer_log = self._answer_log, []
        if not log:
            return {"qid": np.zeros(0, np.int64),
                    "kind": np.zeros(0, np.int64),
                    "ok": np.zeros(0, bool),
                    "tick": np.zeros(0, np.int64),
                    "issue": np.zeros(0, np.int64),
                    "vec": np.zeros((0, self.d_out), np.float32),
                    "score": np.zeros(0, np.float32)}
        return {k: np.concatenate([chunk[k] for chunk in log])
                for k in log[0]}

    def _accumulate(self, stats_all, ticks: int, qstats=None,
                    occ_rows=None):
        """Fold a launch's per-layer stats, summed over its `ticks`
        micro-ticks, into StreamMetrics.

        occ_rows (telemetry plane): [ticks, len(TRACE_DEVICE_COLS)] int
        per-tick occupancy rows off the device — backlog integrals add,
        the peak gauges fold with max (their scan SUM is meaningless)."""
        m = self._metrics
        m.ticks += ticks
        m.wire_bytes += ticks * self._wire_bytes_per_tick
        m.deliver_lane_rows += ticks * self._lane_rows_per_tick
        for s in stats_all:
            m.reduce_msgs += int(s.reduce_msgs)
            m.broadcast_msgs += int(s.broadcast_msgs)
            m.cross_part_msgs += int(s.cross_part_msgs)
            m.dropped += int(s.dropped)
            m.wire_rows += int(s.wire_rows)
            m.route_deferred += int(s.route_deferred)
            m.route_dropped += int(s.route_dropped)
            m.suppressed += int(s.n_suppressed)
            m.deliver_rows += int(s.deliver_rows)
            m.occ_defer_ticks += int(s.occ_bc_defer) + int(s.occ_rmi_defer)
            m.busy_logical += np.asarray(s.busy, np.int64)
        m.emitted_total += int(stats_all[-1].emitted)
        if occ_rows is not None:
            occ = np.asarray(occ_rows).reshape(-1, len(TRACE_DEVICE_COLS))
            ci = {c: i for i, c in enumerate(TRACE_DEVICE_COLS)}
            if occ.size:
                m.route_peak = max(m.route_peak,
                                   int(occ[:, ci["route_peak"]].max()))
                m.outbox_peak = max(m.outbox_peak,
                                    int(occ[:, ci["outbox_demand"]].max()))
                m.outbox_part_peak = max(
                    m.outbox_part_peak,
                    int(occ[:, ci["outbox_part_peak"]].max()))
        if qstats is not None:
            m.queries_admitted += int(qstats.admitted)
            m.queries_answered += int(qstats.answered)
            m.queries_dropped += int(qstats.dropped)
            m.query_hold_ticks += int(qstats.held_ticks)
            # held_ticks and wire_backlog sum end-of-tick gauges: zero
            # means the table and the wire were empty when the launch
            # ended
            self._queries_held = bool(int(qstats.held_ticks)
                                      or int(qstats.wire_backlog))

    def _trace_ticks(self, occ_rows, tick0, wall_s, host_s, counts,
                     stats_all, ticks: int):
        """Telemetry-plane host side: append per-tick trace rows and feed
        the straggler mitigator. No-op when telemetry is off.

        occ_rows: [ticks, C] device occupancy rows; counts: one (edges,
        feats, queries, labels) ingest tuple per tick. A launch of several
        ticks spreads its wall and staging times evenly over them
        (amortized=1)."""
        if self.trace is None:
            return
        occ = np.asarray(occ_rows).reshape(-1, len(TRACE_DEVICE_COLS))
        per = wall_s / max(ticks, 1)
        host_per = host_s / max(ticks, 1)
        for i, (e, f, q, l) in enumerate(counts):
            self.trace.append(
                {"tick": tick0 + i, "ticks": 1, "wall_s": per,
                 "host_s": host_per, "amortized": int(ticks > 1),
                 "wire_bytes": self._wire_bytes_per_tick,
                 "edges_in": e, "feats_in": f, "queries_in": q,
                 "labels_in": l},
                occ[i])
        # straggler feed: per-part busy proxies folded to their shard
        busy = np.zeros(self.cfg.n_parts, np.int64)
        for s in stats_all:
            busy += np.asarray(jax.device_get(s.busy), np.int64)
        shards = busy.reshape(max(self._n_data, 1), -1).sum(axis=1)
        self.straggler.observe_tick(per, shards)

    def span(self, name: str, step: bool = False):
        """A host span of the current launch (`telemetry/spans.py`),
        totalled into `metrics.spans`."""
        return self._clock.span(name, self._metrics.launches, step)

    def _count_staged(self, counts, fb, batches) -> None:
        """Fold one launch's staged batches into the staging counters:
        `counts` holds a (edges, feats, queries, labels) tuple per tick,
        `fb` is the launch's feature batch and `batches` every batch
        handed to the launch."""
        m = self._metrics
        m.edges_staged += sum(c[0] for c in counts)
        m.feat_rows_staged += sum(c[1] for c in counts)
        m.feat_slots_uploaded += int(fb.valid.size)
        m.upload_bytes += sum(int(x.nbytes) for x in jax.tree.leaves(batches))

    def chunk_stream(self, edges, feats, tick_edges: int,
                     feat_with_first_edge: bool = True, seen=None):
        """Cut an edge stream into micro-tick chunks + aligned feature
        events (each vertex's feature fires in the tick of its first edge).
        One cut whatever the launch size, so tick boundaries agree across
        launch sizes — serving loops that chunk a stream in several calls
        pass a persistent `seen` set so features still fire exactly once."""
        seen = set() if seen is None else seen
        e_chunks, f_chunks = [], []
        with self.span("d3.chunk"):
            for lo in range(0, len(edges), tick_edges):
                chunk = edges[lo: lo + tick_edges]
                f_events = []
                if feat_with_first_edge:
                    for u in chunk.reshape(-1):
                        u = int(u)
                        if u not in seen and u in feats:
                            seen.add(u)
                            f_events.append((u, feats[u]))
                e_chunks.append(chunk)
                f_chunks.append(f_events)
        return e_chunks, f_chunks

    # ------------------------------------------------------ super-tick path
    def _stage_super_batches(self, edge_chunks, feat_chunks, query_chunks,
                             label_chunks):
        """Host staging: build T per-tick padded batches, stack along T.

        Returns (fb, eb, rb, vb, qb, lb) pytrees with a leading [T] axis —
        the xs of the super-tick scan. Host partitioner state advances tick
        by tick, as T one-tick launches would advance it; query issue
        ticks are stamped with the tick the scan will admit them in.
        """
        with self.span("d3.stage"):
            ebs, rbs, vbs, fbs, qbs, lbs = [], [], [], [], [], []
            for i, (edges_t, feats_t, queries_t, labels_t) in enumerate(
                    zip(edge_chunks, feat_chunks, query_chunks,
                        label_chunks)):
                eb, rb, vb, fb, qb, lb = self._build_batches(
                    edges_t, feats_t, queries_t, self.now + i, labels_t)
                ebs.append(eb)
                rbs.append(rb)
                vbs.append(vb)
                fbs.append(fb)
                qbs.append(qb)
                lbs.append(lb)
            with self.span("d3.stage.pack"):
                return (ev.stack_batches(fbs), ev.stack_batches(ebs),
                        ev.stack_batches(rbs), ev.stack_batches(vbs),
                        ev.stack_batches(qbs), ev.stack_batches(lbs))

    def run_super_tick(self, edge_chunks=None, feat_chunks=None,
                       T: Optional[int] = None, window=None,
                       quiet0: int = 0, query_chunks=None,
                       label_chunks=None):
        """Advance T micro-ticks in ONE device program (`lax.scan`).

        edge_chunks: list of per-tick edge arrays (or None entries);
        feat_chunks: list of per-tick [(vid, vec), ...] lists (or None);
        query_chunks: list of per-tick query-request lists (or None) —
        the tick() `queries` format, admitted at their staged tick;
        label_chunks: list of per-tick [(vid, gold_class), ...] lists (or
        None) — the tick() `labels` format, admitted at their staged tick.
        Shorter lists are padded with empty ticks up to T.
        quiet0 seeds the consecutive-quiet-tick counter (flush chaining).

        Returns a `LaunchResult`: (per-layer summed TickStats tuple,
        quiet_ticks), read from the launch's one host sync (a device_get
        that also carries the T ticks' stacked answers and the summed
        QueryStats; training-plane progress stays device-resident until
        `train_stats()` is read).

        The launch stages, dispatches, then reads the launch before it
        if that one is still pending. It syncs at once itself when the
        host must see its outputs before the next launch: it runs one
        tick (a host that steps tick by tick reads each: `tick`,
        `flush`, one-tick sessions), it admits a query or a label, a
        query admitted earlier was still held when the last read launch
        ended, the telemetry plane is on (trace rows take the launch's
        own wall time), or it stages no edge or feature event (a flush
        launch, read for its quiet counter).
        Otherwise it stays PENDING: the next launch is staged while the
        device runs it, and reading the returned value, `metrics`,
        `settle()`, the next launch (`tick` and `flush` launch too),
        `train_stats`, `reshard`, `read_nodes` / `embeddings`,
        `save_trace` or a checkpoint save reads it. A pending launch
        carries no answers, so `drain_answers` need not wait for it. An
        error raised while reading it surfaces at the latest in the next
        of those calls.
        """
        edge_chunks = list(edge_chunks) if edge_chunks is not None else []
        feat_chunks = list(feat_chunks) if feat_chunks is not None else []
        query_chunks = list(query_chunks) if query_chunks is not None else []
        label_chunks = list(label_chunks) if label_chunks is not None else []
        n = max(len(edge_chunks), len(feat_chunks), len(query_chunks),
                len(label_chunks), 1)
        T = int(T) if T is not None else n
        assert T >= n, f"T={T} smaller than the {n} staged ticks"
        edge_chunks += [None] * (T - len(edge_chunks))
        feat_chunks += [None] * (T - len(feat_chunks))
        query_chunks += [None] * (T - len(query_chunks))
        label_chunks += [None] * (T - len(label_chunks))
        counts = [(len(e) if e is not None else 0,
                   len(f) if f else 0, len(q) if q else 0,
                   len(l) if l else 0)
                  for e, f, q, l in zip(edge_chunks, feat_chunks,
                                        query_chunks, label_chunks)]
        sync_now = (T == 1 or self.cfg.telemetry or self._queries_held
                    or any(q or l for _, _, q, l in counts)
                    or not any(e or f for e, f, _, _ in counts))
        launch = _Launch(ticks=T, tick0=self.now, counts=counts)
        self._metrics.launches += 1
        with self.span("d3.launch", step=True) as span:
            launch.span = span
            s0 = self._clock.total("d3.stage")
            try:
                batches = self._stage_super_batches(
                    edge_chunks, feat_chunks, query_chunks, label_chunks)
            except Exception:
                self.settle()          # the launch before still folds
                raise
            launch.host_s = self._clock.total("d3.stage") - s0
            self._count_staged(counts, batches[0], batches)
            launch.outs = self._dispatch_super(batches, T, window, quiet0)
            prev, self._pending = self._pending, launch
            if prev is not None:
                self._metrics.launches_overlapped += 1
                self._fold(prev)
            if sync_now:
                self.settle()
        return LaunchResult(self, launch)

    def _dispatch_super(self, batches, T: int, window, quiet0: int):
        """Hand the staged launch to the device and install its carry.
        Returns its unread outputs: (summed stats, quiet counter, summed
        bubble counters or None on a 1-D mesh, summed QueryStats,
        stacked answers, occupancy rows)."""
        cfg = self.cfg
        carry = st.PipelineCarry(
            topo=self.topo, layers=tuple(self.states), sink=self.sink,
            sink_seen=self.sink_seen, queries=self.queries,
            now=jnp.asarray(self.now, jnp.int32),
            quiet=jnp.asarray(quiet0, jnp.int32),
            stage_ring=self.stage_ring, train=self.train_state)
        wconf = window or cfg.window
        outbox_cap = cfg.capacities().outbox
        with self.span("d3.dispatch"):
            if self.n_stages > 1:
                final, stats, idle, qstats, answers, occ = \
                    _super_tick_scan_2d(
                        self.rounds, self._staged_params(), carry, batches,
                        wconf, outbox_cap, self.router, self.delivery,
                        self.mesh, cfg.delta_eps, self.train_cfg,
                        self._head, self._acts, cfg.telemetry)
            else:
                final, stats, qstats, answers, occ = _super_tick_scan(
                    tuple(self.layers), self.params, carry, batches, wconf,
                    outbox_cap, self.router, self.delivery, self.mesh,
                    cfg.delta_eps, self.train_cfg, self._head,
                    cfg.telemetry)
                idle = None
        self.topo = final.topo
        self.states = list(final.layers)
        self.sink = final.sink
        self.sink_seen = final.sink_seen
        self.queries = final.queries
        self.stage_ring = final.stage_ring
        self.train_state = final.train
        self._sync_params_from_train()
        self.now += T
        # `quiet` is read from this carry; the next launch donates a
        # fresh one, so the read stays valid after it
        return stats, final.quiet, idle, qstats, answers, occ

    def settle(self) -> None:
        """Read the pending super-tick launch, if there is one, and fold
        it into the host's counters, answers and trace."""
        launch, self._pending = self._pending, None
        if launch is not None:
            self._fold(launch)

    def _fold(self, launch: "_Launch") -> None:
        """The launch's one host sync (summed stats, quiet counter, query
        stats, the T ticks' stacked answers and the telemetry occupancy
        rows in ONE device_get), then its harvest."""
        with self.span("d3.sync"):
            stats, quiet, idle, qstats, answers, occ = jax.device_get(
                launch.outs)
        launch.outs = None
        with self.span("d3.harvest"):
            self._harvest_answers(answers)
            if idle is not None:
                stats = self._unstack_stats(stats)
                self._metrics.stage_idle += int(np.sum(idle))
            occ_np = np.asarray(occ) if self.trace is not None else None
            self._accumulate(stats, ticks=launch.ticks, qstats=qstats,
                             occ_rows=occ_np)
            self._trace_ticks(occ_np, launch.tick0, launch.span.elapsed(),
                              launch.host_s, launch.counts, stats,
                              launch.ticks)
        launch.result = (stats, int(quiet))

    def run_stream_super(self, edges: np.ndarray, feats: dict,
                         tick_edges: int = 256, super_ticks: int = 16,
                         feat_with_first_edge: bool = True):
        """Stream an edge list (+ node features, see `run_stream`)
        through the pipeline, `super_ticks` micro-ticks per launch.

        Cuts the stream into `tick_edges`-sized micro-ticks, groups them
        into super-ticks of `super_ticks` ticks each (the tail group is
        padded with empty ticks so every launch reuses one compiled scan).
        """
        e_chunks, f_chunks = self.chunk_stream(edges, feats, tick_edges,
                                               feat_with_first_edge)
        for lo in range(0, len(e_chunks), super_ticks):
            self.run_super_tick(e_chunks[lo: lo + super_ticks],
                                f_chunks[lo: lo + super_ticks],
                                T=super_ticks)
        return self

    def flush_super(self, max_ticks: int = 64, T: int = 8,
                    drain: bool = True) -> int:
        """Run empty ticks, T a launch, until device quiescence; returns
        the ticks run (a multiple of T, or max_ticks). `drain` as in
        `flush`.

        The consecutive-quiet counter lives in the scan carry; the host
        reads it once per super-tick and re-seeds the next launch through
        the coordinator's public seed_quiet(). Every flush launch stages
        no event and so syncs at once; the first one also reads the
        launch left pending before the flush."""
        term = TerminationCoordinator()
        override = win.WindowConfig(kind=win.STREAMING) if drain else None
        ran = 0
        with self.span("d3.drain"):
            while ran < max_ticks:
                step = min(T, max_ticks - ran)
                self._metrics.drain_launches += 1
                _, quiet = self.run_super_tick(T=step, window=override,
                                               quiet0=term.seed_quiet())
                ran += step
                if term.observe_flag(quiet):
                    return ran
        raise RuntimeError("pipeline failed to terminate "
                           f"within {max_ticks} flush ticks")

    def run_stream(self, edges: np.ndarray, feats: dict,
                   tick_edges: int = 256, feat_with_first_edge: bool = True):
        """Stream an edge list (+ node features) through the pipeline, one
        micro-tick a launch (`run_stream_super` at super_ticks=1).

        feats: {vid: np.ndarray} — each vertex's feature event is injected
        in the tick its first edge appears (feature stream aligned with the
        topology stream, as in the paper's temporal edge-list datasets).
        """
        return self.run_stream_super(
            edges, feats, tick_edges=tick_edges, super_ticks=1,
            feat_with_first_edge=feat_with_first_edge)

    def flush(self, max_ticks: int = 64, drain: bool = True) -> int:
        """Run empty ticks, one a launch (`flush_super` at T=1), until the
        pipeline is quiescent; returns the ticks run.

        drain=True forces pending windows due immediately (streaming
        eviction) — the training coordinator's flush semantics (§4.3.1).
        drain=False waits for the scheduled timers (pure §5.3 behaviour)."""
        return self.flush_super(max_ticks, T=1, drain=drain)

    # ------------------------------------------------------------- queries
    def read_nodes(self, vids) -> dict:
        """Device-side partial gather of sink embeddings for a vid set.

        Only the requested rows are gathered (on device, from the live —
        possibly sharded — sink) and transferred; vids the partitioner has
        never seen, or whose master never materialized an embedding, are
        absent from the result. This is the host-side oracle of the query
        plane's stale_ok reads: a stale_ok answer at tick t bit-matches
        `read_nodes` called right after tick t.
        """
        self.settle()
        vids = np.asarray(list(vids) if not isinstance(vids, np.ndarray)
                          else vids, np.int64).reshape(-1)
        t = self.part.t
        vids = vids[(vids >= 0) & (vids < t.max_nodes)]
        vids = vids[t.master[vids] >= 0]
        if vids.size == 0:
            return {}
        p = jnp.asarray(t.master[vids])
        s = jnp.asarray(t.master_slot[vids])
        vecs, seen = jax.device_get((self.sink[p, s], self.sink_seen[p, s]))
        return {int(v): vecs[i] for i, v in enumerate(vids) if seen[i]}

    def embeddings(self) -> dict:
        """Materialized final-layer embeddings {vid: vector} (masters) —
        a thin wrapper over `read_nodes` for every vid with a master."""
        return self.read_nodes(np.flatnonzero(self.part.t.master >= 0))

    def physical_busy_per_layer(self):
        """Per-layer physical busy vectors under the explosion factor."""
        cfg = self.cfg
        pars = layer_parallelisms(cfg.base_parallelism, cfg.explosion,
                                  len(self.layers), cfg.n_parts)
        return [physical_busy(self.metrics.busy_logical, p, cfg.n_parts)
                for p in pars]


def _occ_row(stats_all, qstats, ts, router, stage: bool = False):
    """The telemetry plane's per-tick device occupancy row — int32
    [len(TRACE_DEVICE_COLS)] in exactly `telemetry/trace.py`'s column
    order. All entries are EXACT integers, already reduced over the data
    axis by the tick body; `stage=True` (the 2-D program) additionally
    folds the per-stage partial stats over the stage axis — additive
    counters with psum_stage, the peak gauges with pmax_stage, and the
    final layer's emissions masked to stage S-1 (layer L-1 lives there).
    Query/train entries are stage-replicated already and skip the stage
    reduction."""
    if stage:
        add, mx = router.psum_stage, router.pmax_stage
        last_w = (router.stage_index()
                  == jnp.int32(router.n_stages - 1)).astype(jnp.int32)
    else:
        add = mx = lambda x: x
        last_w = jnp.int32(1)
    fsum = lambda f: add(sum(getattr(s, f) for s in stats_all))

    def fmax(vals):
        m = vals[0]
        for v in vals[1:]:
            m = jnp.maximum(m, v)
        return mx(m)

    z = jnp.zeros((), jnp.int32)
    if ts is not None:
        labeled = router.psum(jnp.sum(ts.label_mask.astype(jnp.int32)))
        dirty = router.psum(jnp.sum(
            (ts.dirty & ts.label_mask).astype(jnp.int32)))
    else:
        labeled, dirty = z, z
    row = (
        add(stats_all[-1].emitted * last_w),            # emitted_final
        fsum("emitted"),                                # emitted_sum
        fsum("reduce_msgs"),
        fsum("broadcast_msgs"),
        fsum("wire_rows"),
        fsum("route_deferred"),
        fsum("route_dropped"),
        fsum("dropped"),
        fsum("n_suppressed"),                           # suppressed
        fsum("occ_bc_defer"),
        fsum("occ_rmi_defer"),
        fmax([s.route_peak for s in stats_all]),        # route_peak
        fmax([s.emitted + s.dropped
              for s in stats_all]),                     # outbox_demand
        fmax([s.outbox_part_peak
              for s in stats_all]),                     # outbox_part_peak
        qstats.held_ticks,                              # query_pending
        qstats.wire_backlog,                            # query_backlog
        labeled,                                        # train_labeled
        dirty,                                          # train_dirty
        qstats.admitted,                                # q_admitted
        qstats.answered,                                # q_answered
        qstats.dropped,                                 # q_dropped
    )
    assert len(row) == len(TRACE_DEVICE_COLS)
    return jnp.stack([jnp.asarray(v, jnp.int32) for v in row])


def _zero_occ_row():
    return jnp.zeros((len(TRACE_DEVICE_COLS),), jnp.int32)


def _sink_update_body(sink, seen, fb: ev.FeatBatch, part0=0):
    P_loc, N, d = sink.shape
    idx, _ = st.local_index(fb.part, fb.slot, part0, P_loc, N, fb.valid)
    sink = sink.reshape(P_loc * N, d).at[idx].set(fb.feat, mode="drop")
    seen = seen.reshape(P_loc * N).at[idx].set(True, mode="drop")
    return sink.reshape(P_loc, N, d), seen.reshape(P_loc, N)


def _tick_program(layers, params, topo, states, sink, sink_seen, queries,
                  inbox, eb, rb, vb, qb, lb, now, wconf, outbox_cap,
                  router, delivery, delta_eps=0.0, ts=None, tcfg=None,
                  head=None, telemetry=False):
    """ONE full micro-tick over the local part block: topology application,
    the query plane's admit/head-hop stage (start-of-tick), L staged layer
    ticks — with the query wire lane FUSED into layer 0's round-B exchange
    (one all_to_all carries both, ISSUE 5) — the sink update, the query
    plane's answer stage, and the TRAINING plane's windowed online step
    (end-of-tick, ISSUE 8; `tcfg is None` — the train_cap=0 default —
    compiles the whole plane away and the program is bit-for-bit the
    four-plane tick). Runs directly under the LocalRouter and as the
    shard_map body under the MeshRouter — the two routers and the two
    delivery backends share this program, one step of the super-tick
    scan."""
    part0 = router.part0()
    with jax.named_scope("d3.topo"):
        topo = st.apply_vertex_batch(topo, vb, part0)
        topo = st.apply_repl_batch(topo, rb, part0)
        topo = st.apply_edge_batch(topo, eb, part0)
    with jax.named_scope("d3.query"):
        # does this tick ingest anything that could move state?
        # (replicated batches — every device votes identically);
        # consistent link heads only fire when the whole tick is provably
        # still (serve/query.py)
        batch_work = (jnp.any(inbox.valid) | jnp.any(eb.valid)
                      | jnp.any(rb.valid))
        queries, wire, adm_drop, n_adm = query_admit_stage(
            queries, qb, states, sink, sink_seen, router, batch_work)
    wire_d = None
    new_states, stats_all = [], []
    for li, layer in enumerate(layers):
        # topology reaches every layer; features only layer 0 (Splitter);
        # the query wire rides layer 0's round-B collective. With the
        # training plane on, the forward reads the LIVE trained params.
        lp = ts.params[f"l{li}"] if tcfg is not None else params[f"l{li}"]
        extra = ((wire, (queries.wire_defer, queries.wire_defer_ok))
                 if li == 0 and wire is not None else None)
        with jax.named_scope(f"d3.layer{li}"):
            ls, outbox, stats, extra_out = layer_tick_body(
                layer, lp, topo, states[li], inbox, eb, rb,
                now, wconf, outbox_cap, router, delivery, extra_lane=extra,
                delta_eps=delta_eps, telemetry=telemetry)
        if extra is not None:
            wire_d, (wdb, wdo) = extra_out
            queries = replace(queries, wire_defer=wdb, wire_defer_ok=wdo)
        new_states.append(ls)
        stats_all.append(stats)
        inbox = outbox
    # sink: final-layer emissions materialize the embedding table
    with jax.named_scope("d3.sink"):
        sink, sink_seen = _sink_update_body(sink, sink_seen, inbox, part0)
    # query plane: answer point queries from the fresh sink
    with jax.named_scope("d3.query"):
        queries, ans, qstats = query_answer_stage(
            queries, wire_d, qb, adm_drop, n_adm, tuple(new_states), sink,
            sink_seen, now, stats_all, router)
    # training plane: one windowed online step through the live state
    new_ts = ts
    if tcfg is not None:
        # 1-D stats scalars are already globally psum'd by the tick body
        moved = sum(moved_msgs(s) for s in stats_all)
        layers_bw = tuple((layers[li], ts.params[f"l{li}"], False)
                          for li in range(len(layers)))
        layer_feats = tuple(
            (new_states[li].feat, new_states[li].agg, new_states[li].agg_cnt)
            for li in range(len(layers)))
        with jax.named_scope("d3.train"):
            new_ts = train_stage(tcfg, head, layers_bw, layer_feats, topo,
                                 sink, sink_seen, ts, lb, inbox, now, moved,
                                 router, part0)
    # telemetry plane: the per-tick occupancy row (trace.py column order)
    occ = (_occ_row(stats_all, qstats, new_ts, router) if telemetry
           else _zero_occ_row())
    return (topo, tuple(new_states), sink, sink_seen, queries,
            tuple(stats_all), ans, qstats, new_ts, occ)


@partial(jax.jit, static_argnames=("layers", "wconf", "outbox_cap",
                                   "router", "delivery", "mesh",
                                   "delta_eps", "tcfg", "head",
                                   "telemetry"),
         donate_argnums=(2,))
def _super_tick_scan(layers, params, carry: st.PipelineCarry, batches,
                     wconf: win.WindowConfig, outbox_cap: int, router,
                     delivery=None, mesh=None, delta_eps=0.0, tcfg=None,
                     head=None, telemetry=False):
    """T micro-ticks x L layers as one `lax.scan` — the super-tick body.

    carry (donated): PipelineCarry — topology, per-layer states, sink,
    the pending-query table, the training-plane TrainState (None when
    the plane is off) and the tick clock / quiet counter, all
    device-resident (and part-sharded when a mesh is given: the scan runs
    INSIDE the shard_map, so the carry never leaves its owning shard
    between ticks).
    batches: (fb, eb, rb, vb, qb, lb) pytrees with leading [T] axis (xs).
    Returns (final carry, per-layer TickStats summed over the T ticks,
    summed QueryStats, per-tick stacked AnswerBatch and the per-tick
    [T, len(TRACE_DEVICE_COLS)] occupancy rows — the scan's ys; the occ
    rows are static zeros unless `telemetry`).
    """
    def scan_prog(params, carry, batches):
        n_parts_loc = carry.topo.n_parts          # LOCAL block under mesh

        def body(state, batch_t):
            c, ssum, qsum = state
            fb, eb, rb, vb, qb, lb = batch_t
            (topo, new_layers, sink, sink_seen, queries, stats_t, ans,
             qstats_t, new_ts, occ) = _tick_program(
                layers, params, c.topo, c.layers, c.sink, c.sink_seen,
                c.queries, fb, eb, rb, vb, qb, lb, c.now, wconf,
                outbox_cap, router, delivery, delta_eps, c.train, tcfg,
                head, telemetry)
            with jax.named_scope("d3.quiet"):
                quiet = quiet_update(c.quiet, new_layers, stats_t, router,
                                     queries=queries)
            new_c = st.PipelineCarry(
                topo=topo, layers=new_layers, sink=sink,
                sink_seen=sink_seen, queries=queries,
                now=c.now + jnp.int32(1), quiet=quiet, train=new_ts)
            ssum = tuple(add_stats(a, b) for a, b in zip(ssum, stats_t))
            return (new_c, ssum, add_query_stats(qsum, qstats_t)), \
                (ans, occ)

        zeros = tuple(zero_stats(n_parts_loc) for _ in layers)
        (final, stats_sum, qstats_sum), (answers, occ_t) = jax.lax.scan(
            body, (carry, zeros, zero_query_stats()), batches)
        return final, stats_sum, qstats_sum, answers, occ_t

    if mesh is None:
        return scan_prog(params, carry, batches)
    cp = carry_pspecs(len(layers),
                      train=(train_pspecs(carry.train)
                             if tcfg is not None else None))
    sharded = jax.shard_map(scan_prog, mesh=mesh,
                            in_specs=(P(), cp, P()),
                            out_specs=(cp, stats_pspecs(len(layers)), P(),
                                       P(None, "data"), P()),
                            check_vma=False)
    return sharded(params, carry, batches)


def lower_super_tick(model, cfg: PipelineConfig, T: int, window=None,
                     sharding=None, mesh=None):
    """Lower the super-tick program that `D3Pipeline(model, params, cfg,
    mesh=mesh)` (1-D mesh or none, no training plane) launches for T
    micro-ticks — from shapes alone. Nothing is allocated, so a
    configuration can be sized (`.compile().memory_analysis()`) before
    its tables exist, and compiled for devices that are described but
    not attached (`jax.experimental.topologies`): `sharding` places a
    mesh-less program on one such device, `mesh` may be built from them.
    `window` defaults to cfg.window; a drain flush launches the STREAMING
    program."""
    def build():
        pipe = D3Pipeline(model, model.init(jax.random.key(0)), cfg)
        carry = st.PipelineCarry(
            topo=pipe.topo, layers=tuple(pipe.states), sink=pipe.sink,
            sink_seen=pipe.sink_seen, queries=pipe.queries,
            now=jnp.int32(0), quiet=jnp.int32(0))
        empty = [None] * T
        return (pipe.params, carry,
                pipe._stage_super_batches(empty, empty, empty, empty))

    args = jax.eval_shape(build)          # (params, carry, batches)
    router, shardings = LocalRouter(cfg.n_parts), None
    if mesh is not None:
        n_dev = int(dict(mesh.shape)["data"])
        assert cfg.route_cap is None or n_dev == 1, \
            "the defer rings of a capped exchange are sized per mesh"
        router = MeshRouter(cfg.n_parts, n_dev, route_cap=cfg.route_cap,
                            pack_backend=cfg.delivery_backend,
                            telemetry=cfg.telemetry)
        rep = NamedSharding(mesh, P())
        shardings = (jax.tree.map(lambda _: rep, args[0]),
                     carry_shardings(mesh, len(model.layers)),
                     jax.tree.map(lambda _: rep, args[2]))
    elif sharding is not None:
        shardings = jax.tree.map(lambda _: sharding, args)
    if shardings is not None:
        args = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            args, shardings)
    return _super_tick_scan.lower(
        tuple(model.layers), *args, window or cfg.window,
        cfg.capacities().outbox, router,
        make_delivery(cfg.delivery_backend), mesh, cfg.delta_eps, None,
        None, cfg.telemetry)


# --------------------------------------------- hybrid-parallel pipeline
def _tick_program_2d(rounds, params, topo, states, sink, sink_seen,
                     queries, ring, inbox, eb, rb, vb, qb, lb, now, wconf,
                     outbox_cap, router, delivery, delta_eps=0.0, ts=None,
                     tcfg=None, head=None, acts=None, telemetry=False):
    """ONE micro-tick of the LAYER-PIPELINED program (ISSUE 7) — the
    shard_map body on a 2-D ("stage", "data") mesh.

    Layer l = r*S + s lives on stage s and runs at round r; each tick
    every stage runs its R = L // S rounds against inputs one hop
    behind: round r's inbox is what the PREVIOUS stage shifted into ring
    slot r last tick, except stage 0 — whose round 0 reads the host
    feature inbox and whose round r > 0 reads slot r-1 (the wrap hop
    from stage S-1's round r-1). Every round's outbox is ppermute'd to
    the next stage IMMEDIATELY after its compute (`stage_shift`) so the
    hop overlaps the remaining rounds' work (double buffering). The
    final layer's rows reach the stage-replicated sink SAME-tick via
    `stage_last`; the redundant wrap copy stage 0 receives in slot R-1
    has its valid column zeroed — it is never a round input.

    Topology batches are stage-replicated and applied identically on
    every stage; the query plane runs identically per stage (its wire
    lane rides round 0's exchange on EVERY stage, which keeps QueryState
    stage-replicated — wire-row telemetry therefore counts the lane S
    times, once per stage's round-0 layer). Per-layer stats stay
    data-psum'd only: each stage's round-r scalars describe layer r*S+s,
    left as [1]-shaped leaves that stack to [S] over the stage out-spec.

    TRAINING plane (ISSUE 8, `tcfg` set): TrainState is stage-REPLICATED
    — the forward takes round r's params from ts.params at the stage's
    own layer index (l = r*S + stage), and after the answer stage every
    stage all_gathers the per-round layer caches over the stage axis and
    runs the SAME deterministic full-L backward, so data-axis collectives
    keep all stage copies bit-identical (acts: the static per-layer 0/1
    activation flags driving the StagedActLayer relu).
    """
    R = len(rounds)
    part0 = router.part0()
    with jax.named_scope("d3.topo"):
        topo = st.apply_vertex_batch(topo, vb, part0)
        topo = st.apply_repl_batch(topo, rb, part0)
        topo = st.apply_edge_batch(topo, eb, part0)
    batch_work = (jnp.any(inbox.valid) | jnp.any(eb.valid)
                  | jnp.any(rb.valid))
    ring = ring[0]                            # local [R, C_buf, W_fb]
    d = states[0].feat.shape[-1]
    proto = ev.empty_feat_batch(ring.shape[1], d)
    vcol = field_col(proto, "valid")
    occ0 = jnp.sum((ring[..., vcol] > 0.5).astype(jnp.int32))
    sq = lambda t: jax.tree.map(lambda a: a[0], t)
    ex = lambda t: jax.tree.map(lambda a: a[None], t)
    sq_states = [sq(s) for s in states]
    with jax.named_scope("d3.query"):
        queries, wire, adm_drop, n_adm = query_admit_stage(
            queries, qb, sq_states, sink, sink_seen, router, batch_work,
            extra_work=occ0)
    host_rows = pad_lane(pack_lane(inbox), ring.shape[1])
    is0 = router.stage_index() == 0
    wire_d = None
    new_states, stats_all, new_slots, idle = [], [], [], []
    out_rows = None
    for r in range(R):
        if r == 0:
            rows_in = jnp.where(is0, host_rows, ring[0])
        else:
            rows_in = jnp.where(is0, ring[r - 1], ring[r])
        round_inbox = unpack_lane(rows_in, proto)
        idle.append((~jnp.any(round_inbox.valid)).astype(jnp.int32))
        extra = ((wire, (queries.wire_defer, queries.wire_defer_ok))
                 if r == 0 and wire is not None else None)
        if tcfg is not None:
            # live trained params: round r's layer on THIS stage is
            # l = r*S + stage_index — gather it from the replicated
            # TrainState by dynamic stage index
            S = router.n_stages
            sidx = router.stage_index()
            stk = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[ts.params[f"l{r * S + s}"] for s in range(S)])
            rparams = {
                "p": jax.tree.map(lambda a: jnp.take(a, sidx, axis=0), stk),
                "act": jnp.take(jnp.asarray(acts, jnp.float32),
                                jnp.int32(r) * S + sidx)}
        else:
            rparams = sq(params[f"r{r}"])
        # round r runs layer r * S + stage on each stage
        with jax.named_scope(f"d3.layer{r}"):
            ls, outbox, stats, extra_out = layer_tick_body(
                rounds[r], rparams, topo, sq_states[r],
                round_inbox, eb, rb, now, wconf, outbox_cap, router,
                delivery, extra_lane=extra, delta_eps=delta_eps,
                telemetry=telemetry)
        if extra is not None:
            wire_d, (wdb, wdo) = extra_out
            queries = replace(queries, wire_defer=wdb, wire_defer_ok=wdo)
        new_states.append(ls)
        stats_all.append(stats)
        out_rows = pad_lane(pack_lane(outbox), ring.shape[1])
        # DOUBLE BUFFER: post the hop now — the remaining rounds' compute
        # overlaps the transfer
        new_slots.append(router.stage_shift(out_rows))
    # same-tick sink feed: the LAST stage's final-round outbox, delivered
    # to every stage's replica of the sink
    with jax.named_scope("d3.sink"):
        final_fb = unpack_lane(router.stage_last(out_rows), proto)
        sink, sink_seen = _sink_update_body(sink, sink_seen, final_fb,
                                            part0)
    # the wrap copy stage 0 received in slot R-1 is the final layer's
    # outbox again (already materialized above) — never a round input
    last = new_slots[R - 1]
    last = last.at[:, vcol].set(jnp.where(is0, 0.0, last[:, vcol]))
    new_slots[R - 1] = last
    new_ring = jnp.stack(new_slots)[None]     # back to [1, R, C_buf, W]
    occ1 = jnp.sum((new_ring[0, ..., vcol] > 0.5).astype(jnp.int32))
    with jax.named_scope("d3.query"):
        queries, ans, qstats = query_answer_stage(
            queries, wire_d, qb, adm_drop, n_adm, tuple(new_states), sink,
            sink_seen, now, stats_all, router, extra_work=occ1)
    # training plane: every stage gathers ALL rounds' caches over the
    # stage axis and runs the identical full-L backward (TrainState stays
    # stage-replicated; see module docstring of core/train_plane.py)
    new_ts = ts
    if tcfg is not None:
        S = router.n_stages
        L = R * S
        # per-stage stats cover only that stage's layers: the movement
        # vote needs the extra stage-axis reduction
        moved = router.psum_stage(sum(moved_msgs(s) for s in stats_all))
        feats_all = [None] * L
        for r in range(R):
            gf = router.stage_gather(new_states[r].feat)
            ga = router.stage_gather(new_states[r].agg)
            gc = router.stage_gather(new_states[r].agg_cnt)
            for s in range(S):
                feats_all[r * S + s] = (gf[s], ga[s], gc[s])
        layers_bw = tuple(
            (rounds[0], {"p": ts.params[f"l{l}"],
                         "act": jnp.asarray(acts[l], jnp.float32)}, True)
            for l in range(L))
        with jax.named_scope("d3.train"):
            new_ts = train_stage(tcfg, head, layers_bw, tuple(feats_all),
                                 topo, sink, sink_seen, ts, lb, final_fb,
                                 now, moved, router, part0)
    idle_v = router.psum(jnp.stack(idle))[None]   # [1, R] -> [S, R]
    # telemetry plane: the occ row folds the per-stage partial stats over
    # the stage axis (psum_stage / pmax_stage) so it is globally
    # replicated — same row on every device, P() out-spec
    occ = (_occ_row(stats_all, qstats, new_ts, router, stage=True)
           if telemetry else _zero_occ_row())
    return (topo, tuple(ex(s) for s in new_states), sink, sink_seen,
            queries, new_ring, tuple(ex(s) for s in stats_all), idle_v,
            ans, qstats, new_ts, occ)


@partial(jax.jit, static_argnames=("rounds", "wconf", "outbox_cap",
                                   "router", "delivery", "mesh",
                                   "delta_eps", "tcfg", "head", "acts",
                                   "telemetry"),
         donate_argnums=(2,))
def _super_tick_scan_2d(rounds, params, carry: st.PipelineCarry, batches,
                        wconf: win.WindowConfig, outbox_cap: int, router,
                        delivery=None, mesh=None, delta_eps=0.0,
                        tcfg=None, head=None, acts=None, telemetry=False):
    """T micro-ticks of the PIPELINED program as one `lax.scan`.

    Same contract as `_super_tick_scan` plus: the donated carry includes
    the inter-stage ring (in-flight rows stay device-resident between
    ticks AND between super-ticks), quiescence counts ring occupancy as
    pending work (a flush super-tick keeps draining until the skewed
    tail has telescoped through every stage), and a third summed output
    carries the [S, R] idle-device-round bubble counters."""
    R = len(rounds)

    def scan_prog(params, carry, batches):
        n_parts_loc = carry.topo.n_parts      # LOCAL block under mesh
        sq = lambda t: jax.tree.map(lambda a: a[0], t)

        def body(state, batch_t):
            c, ssum, isum, qsum = state
            fb, eb, rb, vb, qb, lb = batch_t
            (topo, new_layers, sink, sink_seen, queries, ring, stats_t,
             idle_t, ans, qstats_t, new_ts, occ_row) = _tick_program_2d(
                rounds, params, c.topo, c.layers, c.sink, c.sink_seen,
                c.queries, c.stage_ring, fb, eb, rb, vb, qb, lb, c.now,
                wconf, outbox_cap, router, delivery, delta_eps, c.train,
                tcfg, head, acts, telemetry)
            # rows still in flight between stages are pending work; the
            # valid flag packs LAST in a FeatBatch wire row
            with jax.named_scope("d3.quiet"):
                occ = jnp.sum((ring[0, ..., -1] > 0.5).astype(jnp.int32))
                quiet = quiet_update(c.quiet, [sq(s) for s in new_layers],
                                     [sq(s) for s in stats_t], router,
                                     queries=queries, extra_work=occ)
            new_c = st.PipelineCarry(
                topo=topo, layers=new_layers, sink=sink,
                sink_seen=sink_seen, queries=queries,
                now=c.now + jnp.int32(1), quiet=quiet, stage_ring=ring,
                train=new_ts)
            ssum = tuple(add_stats(a, b) for a, b in zip(ssum, stats_t))
            return (new_c, ssum, isum + idle_t,
                    add_query_stats(qsum, qstats_t)), (ans, occ_row)

        zeros = tuple(jax.tree.map(lambda a: a[None],
                                   zero_stats(n_parts_loc))
                      for _ in range(R))
        izero = jnp.zeros((1, R), jnp.int32)
        (final, ssum, isum, qsum), (answers, occ_t) = jax.lax.scan(
            body, (carry, zeros, izero, zero_query_stats()), batches)
        return final, ssum, isum, qsum, answers, occ_t

    cp = stage_carry_pspecs(R, train=(train_pspecs(carry.train)
                                      if tcfg is not None else None))
    pspec = jax.tree.map(lambda _: P("stage"), params)
    sharded = jax.shard_map(scan_prog, mesh=mesh,
                            in_specs=(pspec, cp, P()),
                            out_specs=(cp, stage_stats_pspecs(R),
                                       P("stage"), P(), P(None, "data"),
                                       P()),
                            check_vma=False)
    return sharded(params, carry, batches)
