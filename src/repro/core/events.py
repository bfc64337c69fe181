"""Unified streaming event format (paper §4.1) + padded device batches.

Host events are light dataclasses; the partitioner turns a tick's worth of
them into fixed-capacity, mask-padded struct-of-arrays batches that the
jitted layer tick consumes. Every batch row is pre-addressed: the host
partitioner resolves global vertex ids to (part, slot) coordinates — the
JVM-side master tables of the paper live in the Partitioner here, so the
device program never needs a hash lookup.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

EDGE_ADD = 1
FEAT_UPDATE = 3


@dataclass(frozen=True)
class EdgeBatch:
    """New-edge records for one tick (device-ready).

    Each record scatters one directed edge (u -> v) into the part that the
    vertex-cut partitioner chose. Both endpoints have (replica) slots there.
    """
    part: jnp.ndarray            # [C] int32 destination part of the record
    edge_slot: jnp.ndarray       # [C] int32 slot in the part's edge table
    src_slot: jnp.ndarray        # [C] int32 local slot of u in `part`
    dst_slot: jnp.ndarray        # [C] int32 local slot of v in `part`
    dst_master_part: jnp.ndarray # [C] int32 master coordinates of v
    dst_master_slot: jnp.ndarray # [C] int32
    valid: jnp.ndarray           # [C] bool

    @property
    def capacity(self):
        return self.part.shape[0]


@dataclass(frozen=True)
class ReplBatch:
    """New replica records: master (part, slot) -> replica (part, slot).

    Scattered into the master part's replication adjacency, used for the
    selectiveBroadcast of features to replicas (paper §5.1).
    """
    part: jnp.ndarray            # [C] int32 master part (where record lives)
    repl_slot: jnp.ndarray       # [C] int32 slot in the replication table
    master_slot: jnp.ndarray     # [C] int32 master's local slot
    rep_part: jnp.ndarray        # [C] int32 replica coordinates
    rep_slot: jnp.ndarray        # [C] int32
    valid: jnp.ndarray           # [C] bool


@dataclass(frozen=True)
class VertexBatch:
    """New vertex (replica) records: existence + mastership flags."""
    part: jnp.ndarray            # [C] int32
    slot: jnp.ndarray            # [C] int32
    is_master: jnp.ndarray       # [C] bool
    valid: jnp.ndarray           # [C] bool


@dataclass(frozen=True)
class FeatBatch:
    """Feature updates addressed to master (part, slot)."""
    part: jnp.ndarray            # [C] int32
    slot: jnp.ndarray            # [C] int32
    feat: jnp.ndarray            # [C, d] float
    valid: jnp.ndarray           # [C] bool

    @property
    def capacity(self):
        return self.part.shape[0]


@dataclass(frozen=True)
class LabelBatch:
    """Label observations addressed to master (part, slot) — the training
    plane's admission unit (capacity = PipelineConfig.train_cap; 0
    compiles the plane away)."""
    part: jnp.ndarray            # [C] int32
    slot: jnp.ndarray            # [C] int32
    label: jnp.ndarray           # [C] int32 gold class
    valid: jnp.ndarray           # [C] bool

    @property
    def capacity(self):
        return self.part.shape[0]


@dataclass(frozen=True)
class MsgBatch:
    """Fixed-capacity, part-addressed message records — the routing plane's
    unit of exchange (one tick's cross-part traffic for one round).

    The compute plane emits these instead of scattering into other parts'
    rows; a Router delivers them (identity on one device, fixed-capacity
    all_to_all on the mesh) and a part-local apply stage consumes them.
    Payload semantics are the consumer's: Round-A broadcast rows SET a
    feature value, Round-B RMI rows ADD an aggregator (delta, dcnt) record.
    """
    part: jnp.ndarray            # [C] int32 destination part (global id)
    slot: jnp.ndarray            # [C] int32 destination slot in that part
    vec: jnp.ndarray             # [C, d] float payload
    cnt: jnp.ndarray             # [C] float count delta (Round B; zeros for A)
    src_part: jnp.ndarray        # [C] int32 emitting part (cross-part stats)
    valid: jnp.ndarray           # [C] bool

    @property
    def capacity(self):
        return self.part.shape[0]

    @property
    def payload_dim(self):
        return self.vec.shape[1]


for _cls, _fields in ((EdgeBatch, ["part", "edge_slot", "src_slot", "dst_slot",
                                   "dst_master_part", "dst_master_slot", "valid"]),
                      (ReplBatch, ["part", "repl_slot", "master_slot",
                                   "rep_part", "rep_slot", "valid"]),
                      (VertexBatch, ["part", "slot", "is_master", "valid"]),
                      (FeatBatch, ["part", "slot", "feat", "valid"]),
                      (LabelBatch, ["part", "slot", "label", "valid"]),
                      (MsgBatch, ["part", "slot", "vec", "cnt", "src_part",
                                  "valid"])):
    jax.tree_util.register_dataclass(_cls, data_fields=_fields, meta_fields=[])


def empty_feat_batch(cap: int, d: int) -> FeatBatch:
    return FeatBatch(part=jnp.zeros((cap,), jnp.int32),
                     slot=jnp.zeros((cap,), jnp.int32),
                     feat=jnp.zeros((cap, d), jnp.float32),
                     valid=jnp.zeros((cap,), bool))


def vertex_batch_from_numpy(rows: dict, cap: int) -> VertexBatch:
    """Padded numpy leaves, as every `*_from_numpy` constructor returns:
    staging stacks a launch's T batches on the host and ships ONE
    transfer per field, so no tick's batch is materialized on device."""
    n = len(rows["part"])
    assert n <= cap, f"vertex batch overflow: {n} > {cap}"
    p = np.zeros((cap,), np.int32)
    s = np.zeros((cap,), np.int32)
    m = np.zeros((cap,), bool)
    v = np.zeros((cap,), bool)
    p[:n] = rows["part"]
    s[:n] = rows["slot"]
    m[:n] = rows["is_master"]
    v[:n] = True
    return VertexBatch(part=p, slot=s, is_master=m, valid=v)


def edge_batch_from_numpy(rows: dict, cap: int) -> EdgeBatch:
    n = len(rows["part"])
    assert n <= cap, f"edge batch overflow: {n} > {cap}"

    def pad(a, dtype=np.int32):
        out = np.zeros((cap,), dtype)
        out[:n] = a
        return out

    valid = np.zeros((cap,), bool)
    valid[:n] = True
    return EdgeBatch(part=pad(rows["part"]), edge_slot=pad(rows["edge_slot"]),
                     src_slot=pad(rows["src_slot"]), dst_slot=pad(rows["dst_slot"]),
                     dst_master_part=pad(rows["dst_master_part"]),
                     dst_master_slot=pad(rows["dst_master_slot"]),
                     valid=valid)


def repl_batch_from_numpy(rows: dict, cap: int) -> ReplBatch:
    n = len(rows["part"])
    assert n <= cap, f"repl batch overflow: {n} > {cap}"

    def pad(a):
        out = np.zeros((cap,), np.int32)
        out[:n] = a
        return out

    valid = np.zeros((cap,), bool)
    valid[:n] = True
    return ReplBatch(part=pad(rows["part"]), repl_slot=pad(rows["repl_slot"]),
                     master_slot=pad(rows["master_slot"]),
                     rep_part=pad(rows["rep_part"]), rep_slot=pad(rows["rep_slot"]),
                     valid=valid)


def concat_msg_batches(a: MsgBatch, b: MsgBatch) -> MsgBatch:
    """Concatenate two MsgBatches along the record axis (same payload dim).

    Round B emits new-edge RMIs and windowed delta RMIs as separate
    batches; one concatenated batch rides the router and the delivery
    backend consumes it as a single fixed-capacity segment reduction.
    """
    return jax.tree.map(lambda x, y: jnp.concatenate([x, y]), a, b)


def coalesce_msg_batch(b: MsgBatch, n_slots: int) -> MsgBatch:
    """Coalesce same-destination records of one MsgBatch (ISSUE 6).

    Aggregator RMIs are additive (core/aggregators.py), so every record
    addressed to the same (part, slot) within one tick can be pre-summed
    BEFORE the routing plane: the coalesced batch keeps the capacity (the
    routing wire is fixed-shape) but carries one live row per distinct
    destination — fewer live rows through the capped all_to_all buckets
    and the defer rings. `n_slots` is the per-part slot count (the
    destination key is part * n_slots + slot).

    Each run's vec/cnt are the sum over the run's records, its src_part
    the first record's (cross-part stats are counted at emission time,
    pre-coalesce — see round_b_emit). The summation ORDER of f32 payloads
    differs from record order, so the delta-gated tick only coalesces in
    approximate mode (delta_eps > 0), where reordering is within budget.
    ADD semantics only — never coalesce a set-semantics lane this way.
    """
    C = b.part.shape[0]
    big = jnp.int32(n_slots) * jnp.max(b.part + 1) + jnp.int32(C)
    key = jnp.where(b.valid, b.part * n_slots + b.slot, big)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    valid_s = b.valid[order]
    head = jnp.concatenate([jnp.ones((1,), bool), key_s[1:] != key_s[:-1]])
    run = jnp.cumsum(head) - 1                   # run index per sorted row
    vec = jnp.zeros_like(b.vec).at[run].add(
        jnp.where(valid_s[:, None], b.vec[order], 0.0))
    cnt = jnp.zeros_like(b.cnt).at[run].add(
        jnp.where(valid_s, b.cnt[order], 0.0))
    # run-head rows carry the destination; non-head rows are dead padding
    pos = jnp.where(head, run, C - 1)
    take = jnp.zeros((C,), jnp.int32).at[pos].max(
        jnp.arange(C, dtype=jnp.int32))          # head's sorted position
    src = order[take]                            # original row of each head
    live = jnp.zeros((C,), bool).at[pos].set(head & valid_s, mode="drop")
    return MsgBatch(part=b.part[src], slot=b.slot[src], vec=vec, cnt=cnt,
                    src_part=b.src_part[src], valid=live)


def stack_batches(batches):
    """Stack same-capacity event batches along a new leading tick axis.

    Host staging for the super-tick driver: T per-tick padded batches become
    one pytree whose leaves carry a leading [T] axis, so `lax.scan` can slice
    one micro-tick per step with zero host round-trips. Stacking happens in
    numpy and ships each field to the device in ONE transfer instead of T.
    All batches must share capacities (they do: capacities derive from the
    PipelineConfig, not from the tick's payload).
    """
    assert batches, "cannot stack an empty batch list"
    return jax.tree.map(
        lambda *xs: jnp.asarray(np.stack([np.asarray(x) for x in xs])),
        *batches)


def label_batch_from_numpy(parts, slots, labels, cap: int) -> LabelBatch:
    n = len(parts)
    assert n <= cap, f"label batch overflow: {n} > {cap}"
    p = np.zeros((cap,), np.int32)
    s = np.zeros((cap,), np.int32)
    y = np.zeros((cap,), np.int32)
    v = np.zeros((cap,), bool)
    p[:n] = parts
    s[:n] = slots
    y[:n] = labels
    v[:n] = True
    return LabelBatch(part=p, slot=s, label=y, valid=v)


def feat_batch_from_numpy(parts, slots, feats, cap: int, d: int) -> FeatBatch:
    n = len(parts)
    assert n <= cap, f"feat batch overflow: {n} > {cap}"
    p = np.zeros((cap,), np.int32)
    s = np.zeros((cap,), np.int32)
    f = np.zeros((cap, d), np.float32)
    v = np.zeros((cap,), bool)
    p[:n] = parts
    s[:n] = slots
    if n:
        f[:n] = feats
    v[:n] = True
    return FeatBatch(part=p, slot=s, feat=f, valid=v)
