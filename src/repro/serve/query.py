"""The QUERY plane (ISSUE 4 tentpole): on-device point queries over the
live sharded state — the paper's "online query setting".

The streaming tick is four planes: COMPUTE (core/tick.py) emits
part-addressed records, ROUTING (dist/router.py) moves them to the owning
device, DELIVERY (core/delivery.py) lands them in state — and QUERY
(here) answers point reads from the state the other three maintain,
without ever materializing the sink to host.

Event records follow the core/events.py MsgBatch conventions: fixed
capacity, mask-padded struct-of-arrays, pre-addressed by the host to
master (part, slot) coordinates so the device never hashes a vertex id.

  QueryBatch  : admissions (host-built, replicated-injected like the
                FeatBatch inbox; each part filters its own rows) AND the
                wire format of the link-score forwarding hop, which rides
                the Router FUSED into layer 0's round-B exchange (ISSUE 5
                lane fusion: one all_to_all launch carries the RMI lane
                and the query wire).
  QueryState  : the per-part pending-query table inside PipelineCarry —
                fixed [P, Q] slots, so held `consistent` queries survive
                super-ticks, donation, sharding and checkpoints.
  AnswerBatch : one row per pending slot per tick; `valid` marks the
                queries answered this tick. The super-tick scan stacks
                these as its ys, so answers ride the existing single
                host sync per super-tick.

Query kinds:

  KIND_EMBED : read the sink embedding of one vertex.
  KIND_LINK  : score an edge (u, v) = <h_u, h_v>, computed ON DEVICE in
               two hops: the query lands at u's master part, gathers h_u
               when ready, and forwards a wire record (vec = h_u) to v's
               master part, where the dot product fires. Both hops can
               complete within one tick when both endpoints are ready.

Freshness modes (per query, the `consistent` flag):

  stale_ok   : answer in the admission tick from the current sink — the
               bounded-staleness read of InkStream/Ripple; bit-equal to
               a host `read_nodes` of the same tick by construction.
  consistent : hold while the target still has dirty/pending window
               state (red_pending | fwd_pending at any layer) OR the
               tick was not globally silent (a message moved, or ANY
               vertex anywhere still holds pending window state whose
               eviction could reach the target) — i.e. answer only at a
               quiescent tick, when every ingested update has fully
               propagated. A consequence: at such a tick every flag is
               clear, so a consistent link's head and tail hops fire in
               the SAME tick — the score is a consistent snapshot.
               The answer tick is recorded for staleness accounting;
               after a drain flush the answers equal the static oracle.

Admission overflow (a full pending table) is never silent: the dropped
records come back as ok=False answer rows in the same tick, so the
client keeps a retriable qid, and QueryStats counts them.

Tick placement (ISSUE 5): the plane runs as TWO stages. Admissions and
the link HEAD hop run at the START of the tick (`query_admit_stage`) so
the wire can share layer 0's round-B all_to_all; the head's h_u read is
therefore the start-of-tick sink (one tick of bounded staleness on the
head endpoint for stale_ok links — the tail endpoint and every EMBED
read stay end-of-tick fresh). `consistent` heads only fire at a
START-silent tick (no pending window state, no deferred wire rows, an
empty update batch), at which nothing can move during the tick, so the
head value equals the end-of-tick value and the two hops of a
consistent link still answer within ONE tick with a consistent
snapshot. Answers (`query_answer_stage`) run after the sink update,
exactly as before. Host qids must stay below 2**24: the packed wire
value-casts ints to f32 (dist/wire.py).

Under wire-lane backpressure (`route_cap` smaller than the tick's wire
traffic) tail records can arrive a tick late; a consistent link then
scores the snapshot of its (quiet) head tick rather than its answer
tick — after a drain flush the two coincide.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.termination import pending_work

# query kinds (host submits EMBED/LINK; LINK_TAIL is the device-internal
# second hop of a link-score query, never admitted from host)
KIND_EMBED = 0
KIND_LINK = 1
KIND_LINK_TAIL = 2


@dataclass(frozen=True)
class QueryBatch:
    """Fixed-capacity query records — admissions and the link-tail wire.

    `part`/`slot` address the record's target master; `part2`/`slot2`
    carry the second endpoint of a KIND_LINK query (the tail hop's
    destination). `vec` is zero on admission and carries h_u on the
    KIND_LINK_TAIL wire. `ok` accumulates the seen-flags of gathered
    endpoints (host sets True; the tail hop ANDs in sink_seen[u]).
    """
    qid: jnp.ndarray          # [C] int32 host-assigned query id
    kind: jnp.ndarray         # [C] int32 KIND_*
    part: jnp.ndarray         # [C] int32 target master part (routing key)
    slot: jnp.ndarray         # [C] int32 target master slot
    part2: jnp.ndarray        # [C] int32 second endpoint master part (LINK)
    slot2: jnp.ndarray        # [C] int32
    consistent: jnp.ndarray   # [C] bool  freshness mode
    ok: jnp.ndarray           # [C] bool  seen-flag accumulator
    issue: jnp.ndarray        # [C] int32 issue tick (host-stamped)
    vec: jnp.ndarray          # [C, d] float payload (tail hop: h_u)
    valid: jnp.ndarray        # [C] bool

    @property
    def capacity(self):
        return self.part.shape[0]


@dataclass(frozen=True)
class QueryState:
    """Per-part pending-query table (the query plane's operator state).

    All arrays are [P, Q] (vec: [P, Q, d]) — part-leading like every
    other carry table, so the same block-sharding, donation and
    checkpoint rules apply. `pending` marks occupied slots; answered or
    forwarded slots free immediately for reuse.
    """
    qid: jnp.ndarray          # [P, Q] int32
    kind: jnp.ndarray         # [P, Q] int32
    slot: jnp.ndarray         # [P, Q] int32 local target slot in this part
    part2: jnp.ndarray        # [P, Q] int32
    slot2: jnp.ndarray        # [P, Q] int32
    consistent: jnp.ndarray   # [P, Q] bool
    ok: jnp.ndarray           # [P, Q] bool
    issue: jnp.ndarray        # [P, Q] int32
    vec: jnp.ndarray          # [P, Q, d] float (h_u for tail-hop rows)
    pending: jnp.ndarray      # [P, Q] bool
    # wire-lane backpressure ring (ISSUE 5): packed QueryBatch rows that
    # overflowed the capped fused exchange, re-entering next tick
    # (dist/wire.py format; [D * K, W] global, block-sharded; K = 0 under
    # the dense default / LocalRouter)
    wire_defer: jnp.ndarray   # [DK, W] f32
    wire_defer_ok: jnp.ndarray  # [DK] bool

    @property
    def query_cap(self):
        return self.qid.shape[1]


@dataclass(frozen=True)
class AnswerBatch:
    """One tick's answers — one row per pending slot, `valid` = answered.

    `vec` holds the embedding for KIND_EMBED rows, `score` the link score
    for KIND_LINK rows (the kind field reports the HOST-facing kind: tail
    hops answer as KIND_LINK). `ok` is False when any gathered endpoint
    had never materialized in the sink.
    """
    qid: jnp.ndarray          # [A] int32
    kind: jnp.ndarray         # [A] int32 (KIND_EMBED | KIND_LINK)
    ok: jnp.ndarray           # [A] bool
    tick: jnp.ndarray         # [A] int32 answer tick
    issue: jnp.ndarray        # [A] int32 issue tick (staleness = tick-issue)
    vec: jnp.ndarray          # [A, d] float
    score: jnp.ndarray        # [A] float
    valid: jnp.ndarray        # [A] bool


@dataclass(frozen=True)
class QueryStats:
    """Per-tick query-plane telemetry (scalars, globally psum'd)."""
    admitted: jnp.ndarray     # queries that found a pending slot
    answered: jnp.ndarray     # answers emitted this tick
    dropped: jnp.ndarray      # admissions lost to a full pending table
    held_ticks: jnp.ndarray   # pending-query-ticks (backlog integral)
    wire_backlog: jnp.ndarray  # wire rows still deferred after this tick
                               # (a gauge: the host flush loop must keep
                               # ticking while it is non-zero)


for _cls, _fields in (
    (QueryBatch, ["qid", "kind", "part", "slot", "part2", "slot2",
                  "consistent", "ok", "issue", "vec", "valid"]),
    (QueryState, ["qid", "kind", "slot", "part2", "slot2", "consistent",
                  "ok", "issue", "vec", "pending", "wire_defer",
                  "wire_defer_ok"]),
    (AnswerBatch, ["qid", "kind", "ok", "tick", "issue", "vec", "score",
                   "valid"]),
    (QueryStats, ["admitted", "answered", "dropped", "held_ticks",
                  "wire_backlog"]),
):
    jax.tree_util.register_dataclass(_cls, data_fields=_fields,
                                     meta_fields=[])


def wire_width(d: int) -> int:
    """Packed row width of the QueryBatch wire lane (dist/wire.py)."""
    from repro.dist.wire import lane_width
    return lane_width(empty_query_batch(1, d))


def init_query_state(n_parts: int, query_cap: int, d: int,
                     wire_defer_rows: int = 0) -> QueryState:
    """wire_defer_rows: GLOBAL (n_devices * per-device) rows of the wire
    lane's backpressure ring — 0 (dense default / off-mesh) compiles the
    deferral path away."""
    zi = lambda: jnp.zeros((n_parts, query_cap), jnp.int32)
    zb = lambda: jnp.zeros((n_parts, query_cap), bool)
    return QueryState(qid=zi(), kind=zi(), slot=zi(), part2=zi(),
                      slot2=zi(), consistent=zb(), ok=zb(), issue=zi(),
                      vec=jnp.zeros((n_parts, query_cap, d), jnp.float32),
                      pending=zb(),
                      wire_defer=jnp.zeros((wire_defer_rows, wire_width(d)),
                                           jnp.float32),
                      wire_defer_ok=jnp.zeros((wire_defer_rows,), bool))


def zero_query_stats() -> QueryStats:
    z = jnp.zeros((), jnp.int32)
    return QueryStats(admitted=z, answered=z, dropped=z, held_ticks=z,
                      wire_backlog=z)


def add_query_stats(a: QueryStats, b: QueryStats) -> QueryStats:
    return jax.tree.map(jnp.add, a, b)


def empty_query_batch(cap: int, d: int) -> QueryBatch:
    """An admission batch with no query, as numpy leaves (host staging
    stacks a launch's batches and uploads each field once)."""
    zi = np.zeros((cap,), np.int32)
    zb = np.zeros((cap,), bool)
    return QueryBatch(qid=zi, kind=zi, part=zi, slot=zi, part2=zi,
                      slot2=zi, consistent=zb, ok=zb, issue=zi,
                      vec=np.zeros((cap, d), np.float32), valid=zb)


def query_batch_from_numpy(rows: dict, cap: int, d: int) -> QueryBatch:
    """rows: {qid, kind, part, slot, part2, slot2, consistent, issue}
    numpy columns (vec is always zero on admission; ok starts True).
    Returns numpy leaves, as every `*_from_numpy` constructor does."""
    n = len(rows["qid"])
    assert n <= cap, f"query batch overflow: {n} > {cap}"

    def pad(a, dtype=np.int32):
        out = np.zeros((cap,), dtype)
        out[:n] = a
        return out

    valid = np.zeros((cap,), bool)
    valid[:n] = True
    ok = np.zeros((cap,), bool)
    ok[:n] = True
    return QueryBatch(qid=pad(rows["qid"]), kind=pad(rows["kind"]),
                      part=pad(rows["part"]), slot=pad(rows["slot"]),
                      part2=pad(rows["part2"]), slot2=pad(rows["slot2"]),
                      consistent=pad(rows["consistent"], bool),
                      ok=ok, issue=pad(rows["issue"]),
                      vec=np.zeros((cap, d), np.float32), valid=valid)


# ===================================================== device-side stages

def admit(qs: QueryState, qb: QueryBatch, part0):
    """Land incoming query records in free pending-table slots.

    Each part ranks its valid arrivals (cumsum over a one-hot membership)
    and assigns them its free slots in ascending order — deterministic
    regardless of router, driver or delivery backend, because the rank
    only depends on record order and LocalRouter/MeshRouter both present
    records in global (source part, slot) order. Arrivals beyond the free
    capacity are DROPPED — the caller turns the returned drop mask into
    ok=False answer rows so the client learns WHICH qids to re-submit.

    Returns (new state, n_admitted, dropped mask [C]).
    """
    P_loc, Q = qs.qid.shape
    lp = qb.part - part0
    ok = qb.valid & (lp >= 0) & (lp < P_loc)
    member = (jnp.where(ok, lp, P_loc)[:, None]
              == jnp.arange(P_loc)[None, :])                     # [C, P]
    rank = jnp.cumsum(member.astype(jnp.int32), axis=0) - 1
    r = jnp.sum(jnp.where(member, rank, 0), axis=1)              # [C]
    # free slot ids per part, ascending (occupied slots sort to the tail)
    free = jnp.sort(jnp.where(qs.pending, Q,
                              jnp.arange(Q)[None, :]), axis=1)   # [P, Q]
    dest = free[jnp.minimum(jnp.maximum(lp, 0), P_loc - 1),
                jnp.minimum(r, Q - 1)]
    admitted = ok & (r < Q) & (dest < Q)
    flat = jnp.where(admitted, lp * Q + dest, P_loc * Q)

    def scat(tbl, val):
        return tbl.reshape(P_loc * Q).at[flat].set(
            val, mode="drop").reshape(P_loc, Q)

    d = qs.vec.shape[-1]
    new = QueryState(
        qid=scat(qs.qid, qb.qid), kind=scat(qs.kind, qb.kind),
        slot=scat(qs.slot, qb.slot), part2=scat(qs.part2, qb.part2),
        slot2=scat(qs.slot2, qb.slot2),
        consistent=scat(qs.consistent, qb.consistent),
        ok=scat(qs.ok, qb.ok), issue=scat(qs.issue, qb.issue),
        vec=qs.vec.reshape(P_loc * Q, d).at[flat].set(
            qb.vec, mode="drop").reshape(P_loc, Q, d),
        pending=scat(qs.pending, admitted),
        wire_defer=qs.wire_defer, wire_defer_ok=qs.wire_defer_ok)
    return new, jnp.sum(admitted), ok & ~admitted


def _drop_answers(qb: QueryBatch, dropped, now, d: int) -> AnswerBatch:
    """Admission-overflow records as ok=False answer rows: the client
    keeps a retriable qid instead of a leaked, forever-outstanding one."""
    C = qb.valid.shape[0]
    return AnswerBatch(
        qid=qb.qid,
        kind=jnp.where(qb.kind == KIND_LINK_TAIL, KIND_LINK, qb.kind),
        ok=jnp.zeros((C,), bool), tick=jnp.full((C,), now, jnp.int32),
        issue=qb.issue, vec=jnp.zeros((C, d), jnp.float32),
        score=jnp.zeros((C,), jnp.float32), valid=dropped)


def _target(qs: QueryState, N: int):
    P_loc, Q = qs.qid.shape
    return (jnp.arange(P_loc)[:, None] * N
            + jnp.clip(qs.slot, 0, N - 1)).reshape(-1)         # [P*Q]


def _empty_answers(d: int) -> AnswerBatch:
    return AnswerBatch(
        qid=jnp.zeros((0,), jnp.int32), kind=jnp.zeros((0,), jnp.int32),
        ok=jnp.zeros((0,), bool), tick=jnp.zeros((0,), jnp.int32),
        issue=jnp.zeros((0,), jnp.int32),
        vec=jnp.zeros((0, d), jnp.float32),
        score=jnp.zeros((0,), jnp.float32), valid=jnp.zeros((0,), bool))


def _plane_work(qs: QueryState, layer_states, router=None, extra_work=None):
    """The shared inputs of BOTH silence gates (start and end of tick):
    per-row clean flags (no red/fwd pending at any layer for that target
    row) and the local pending-work count — the SAME
    `termination.pending_work` aggregation the quiescence gates use, so
    the consistent-snapshot guarantee and flush termination can never
    disagree about what counts as in-flight.

    On a hybrid 2-D mesh each stage holds only ITS layers' states, so
    the per-row dirty flags are OR'd across the stage axis (a row is
    dirty if ANY layer anywhere still has it pending) and the caller's
    `extra_work` carries the inter-stage ring occupancy."""
    P_loc, N = layer_states[0].red_pending.shape
    dirty = jnp.zeros((P_loc, N), bool)
    for ls in layer_states:
        dirty = dirty | ls.red_pending | ls.fwd_pending
    if router is not None and getattr(router, "n_stages", 1) > 1:
        dirty = router.psum_stage(dirty.astype(jnp.int32)) > 0
    return (~dirty.reshape(P_loc * N),
            pending_work(layer_states, qs, extra_work))


def query_admit_stage(qs: QueryState, qb: QueryBatch, layer_states, sink,
                      sink_seen, router, batch_work, extra_work=None):
    """START-of-tick half of the query plane (before the layer ticks).

    1. admit the host's new queries (replicated batch, local filter);
    2. link-score head hop: ready KIND_LINK rows gather h_u from the
       START-of-tick sink and emit a KIND_LINK_TAIL wire record to the
       second endpoint's master part. The returned wire batch rides
       layer 0's round-B exchange (ONE fused all_to_all — ISSUE 5), and
       the delivered records reach `query_answer_stage` the same tick.

    Readiness of consistent heads uses START-silence: no pending window
    state or deferred route/wire rows anywhere (psum'd vote) and an
    empty update batch (`batch_work`) — under which NOTHING can move
    during this tick, so the head's h_u equals its end-of-tick value and
    the link scores a consistent snapshot.

    Returns (new state, wire QueryBatch [P_loc*Q], admission-drop mask,
    n_admitted). Q == 0 short-circuits statically (no wire lane).
    """
    P_loc, Q = qs.qid.shape
    if Q == 0:
        return qs, None, None, jnp.zeros((), jnp.int32)
    part0 = router.part0()
    d = qs.vec.shape[-1]
    N = sink.shape[1]
    sink_flat = sink.reshape(P_loc * N, d)
    seen_flat = sink_seen.reshape(P_loc * N)
    clean_flat, work = _plane_work(qs, layer_states, router, extra_work)
    silent_start = (router.psum_vote(work) == 0) & ~batch_work

    qs, n_adm, drop = admit(qs, qb, part0)

    tgt = _target(qs, N)
    fire_head = (qs.pending & (qs.kind == KIND_LINK)
                 & (~qs.consistent
                    | (clean_flat[tgt] & silent_start).reshape(P_loc, Q)))
    K = qs.wire_defer_ok.shape[0]
    if K:
        # wire-ring headroom gate: a head only fires if the backpressure
        # ring could carry its tail even if NOTHING ships this tick, so
        # the ring structurally cannot overflow and no link query can
        # ever be dropped on the wire (a lost tail would strand its qid).
        # Gated heads stay in the pending table — backpressure propagates
        # to admissions, which answer ok=False retriably when full.
        free = jnp.int32(K) - jnp.sum(qs.wire_defer_ok.astype(jnp.int32))
        fh_flat = fire_head.reshape(-1)
        head_rank = jnp.cumsum(fh_flat.astype(jnp.int32)) - 1
        fire_head = (fh_flat & (head_rank < free)).reshape(P_loc, Q)
    fh = fire_head.reshape(-1)
    wire = QueryBatch(
        qid=qs.qid.reshape(-1), kind=jnp.full((P_loc * Q,), KIND_LINK_TAIL,
                                              jnp.int32),
        part=qs.part2.reshape(-1), slot=qs.slot2.reshape(-1),
        part2=jnp.zeros((P_loc * Q,), jnp.int32),
        slot2=jnp.zeros((P_loc * Q,), jnp.int32),
        consistent=qs.consistent.reshape(-1),
        ok=qs.ok.reshape(-1) & seen_flat[tgt],
        issue=qs.issue.reshape(-1),
        vec=jnp.where(fh[:, None], sink_flat[tgt], 0.0), valid=fh)
    qs = replace(qs, pending=qs.pending & ~fire_head)
    return qs, wire, drop, n_adm


def query_answer_stage(qs: QueryState, wire_d, qb: QueryBatch, drop1,
                       n_adm, layer_states, sink, sink_seen, now,
                       stats_all, router, extra_work=None):
    """END-of-tick half: runs AFTER the sink update so answers read the
    freshest representations.

    1. admit the DELIVERED wire records (link tails — possibly carried
       over from an earlier tick by wire-lane backpressure);
    2. answer: ready KIND_EMBED rows gather the sink row, ready
       KIND_LINK_TAIL rows fire <vec, h_v>; answered slots free. Rows
       dropped by a full pending table answer ok=False instead of
       vanishing (see _drop_answers).

    Readiness: stale_ok rows are always ready; `consistent` rows wait
    for clean target flags AND end-of-tick global silence: no message
    moved this tick (the psum'd stats) and no pending window state,
    deferred route rows, or wire backlog anywhere.

    Returns (new QueryState, AnswerBatch [P_loc*Q + C_adm + |wire_d|],
    QueryStats). Q == 0 short-circuits statically to the exact
    pre-query-plane program.
    """
    P_loc, Q = qs.qid.shape
    d = qs.vec.shape[-1]
    if Q == 0:
        return qs, _empty_answers(d), zero_query_stats()

    part0 = router.part0()
    N = sink.shape[1]
    sink_flat = sink.reshape(P_loc * N, d)
    seen_flat = sink_seen.reshape(P_loc * N)
    clean_flat, timers = _plane_work(qs, layer_states, router, extra_work)
    moved = jnp.zeros((), jnp.int32)
    for s in stats_all:
        moved = moved + s.emitted + s.reduce_msgs + s.broadcast_msgs
    if getattr(router, "n_stages", 1) > 1:
        # 2-D mesh: stats cover this stage's layers only — globalize
        moved = router.psum_stage(moved)
    silent = (moved == 0) & (router.psum_vote(timers) == 0)

    qs, n_adm2, drop2 = admit(qs, wire_d, part0)

    tgt = _target(qs, N)
    fire = (qs.pending & (qs.kind != KIND_LINK)
            & (~qs.consistent
               | (clean_flat[tgt] & silent).reshape(P_loc, Q)))
    ff = fire.reshape(-1)
    h = sink_flat[tgt]
    is_tail = (qs.kind == KIND_LINK_TAIL).reshape(-1)
    score = jnp.sum(qs.vec.reshape(P_loc * Q, d) * h, axis=-1)
    ans = AnswerBatch(
        qid=qs.qid.reshape(-1),
        kind=jnp.where(is_tail, KIND_LINK, qs.kind.reshape(-1)),
        ok=ff & seen_flat[tgt] & jnp.where(is_tail, qs.ok.reshape(-1), True),
        tick=jnp.full((P_loc * Q,), now, jnp.int32),
        issue=qs.issue.reshape(-1),
        vec=jnp.where((ff & ~is_tail)[:, None], h, 0.0),
        score=jnp.where(ff & is_tail, score, 0.0), valid=ff)
    qs = replace(qs, pending=qs.pending & ~fire)

    # overflow-dropped admissions (host batch + wire) answer ok=False
    ans = jax.tree.map(
        lambda *xs: jnp.concatenate(xs),
        ans, _drop_answers(qb, drop1, now, d),
        _drop_answers(wire_d, drop2, now, d))

    psum = router.psum
    del n_adm2                        # tail re-admits are not new client queries
    stats = QueryStats(
        admitted=psum(n_adm),
        answered=psum(jnp.sum(fire)),
        dropped=psum(jnp.sum(drop1) + jnp.sum(drop2)),
        held_ticks=psum(jnp.sum(qs.pending)),
        wire_backlog=psum(jnp.sum(qs.wire_defer_ok.astype(jnp.int32))))
    return qs, ans, stats
