"""Per-family sharding rules for the production mesh (dry-run §Perf).

Rules are heuristics keyed by the ArchSpec family, applied without
allocation to jax.eval_shape trees:

  lm     : tensor parallel — shard the largest axis divisible by the
           "model" axis; embeddings/MoE expert slabs land on their natural
           axis; replicated over data axes (DP handles the batch).
  gnn    : replicated parameters (graphs shard over data axes instead).
  d3gnn  : replicated parameters; the engine shards its part axis itself.
  recsys : embedding tables row-sharded over the model axis (they dwarf
           the dense towers), dense params replicated.

Inputs: leading (batch/part) axis over the data axes when divisible, else
replicated. `spec_tree` maps a rule over an eval_shape tree and returns
NamedShardings ready for jax.jit in_shardings.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes


def _axis_size(mesh: Mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.axis_names else 1


def _model_spec(leaf, mesh: Mesh) -> P:
    """Shard the largest divisible axis over "model"; else replicate."""
    m = _axis_size(mesh, "model")
    if m <= 1 or not hasattr(leaf, "shape") or len(leaf.shape) == 0:
        return P()
    dims = list(leaf.shape)
    order = sorted(range(len(dims)), key=lambda i: -dims[i])
    for i in order:
        if dims[i] % m == 0 and dims[i] >= m:
            spec = [None] * len(dims)
            spec[i] = "model"
            return P(*spec)
    return P()


def _replicated(leaf, mesh: Mesh) -> P:
    return P()


def _recsys_spec(leaf, mesh: Mesh) -> P:
    # row-shard anything that looks like an embedding table (2D and tall)
    if (hasattr(leaf, "shape") and len(leaf.shape) == 2
            and leaf.shape[0] >= 16 * max(1, leaf.shape[1])
            and leaf.shape[0] % max(1, _axis_size(mesh, "model")) == 0):
        return P("model")
    return P()


FAMILY_PARAM_RULES = {
    "lm": _model_spec,
    "gnn": _replicated,
    "d3gnn": _replicated,
    "recsys": _recsys_spec,
}


def spec_tree(tree, rule, mesh: Mesh):
    """Map a (leaf, mesh) -> PartitionSpec rule into NamedShardings."""
    return jax.tree.map(lambda l: NamedSharding(mesh, rule(l, mesh)), tree)


def _batch_sharding(leaf, mesh: Mesh) -> NamedSharding:
    axes = data_axes(mesh)
    n = int(np.prod([_axis_size(mesh, a) for a in axes])) if axes else 1
    if (n > 1 and hasattr(leaf, "shape") and len(leaf.shape) >= 1
            and leaf.shape[0] % n == 0 and leaf.shape[0] >= n):
        return NamedSharding(mesh, P(axes))
    return NamedSharding(mesh, P())


def _input_rule(in_specs: dict, mesh: Mesh, kind: str) -> dict:
    return {k: jax.tree.map(lambda l: _batch_sharding(l, mesh), v)
            for k, v in in_specs.items()}


FAMILY_INPUT_RULES = {
    "lm": _input_rule,
    "gnn": _input_rule,
    "d3gnn": _input_rule,
    "recsys": _input_rule,
}


# ------------------------------------------------ streaming-engine carry
# NamedSharding rules for the streaming `PipelineCarry` (core/state.py):
# every [P, ...] table is block-sharded over the ("data",) axis so the
# donated super-tick carry stays device-resident at its owning shard; the
# CountMinSketch, tick clock and quiet counter are replicated (the tick
# body keeps them consistent via psum). The pspec tree doubles as the
# shard_map in/out specs for the tick program (core/pipeline.py).

def _carry_tree(n_layers: int, part, rep, train=None):
    """Build a PipelineCarry-shaped tree with `part` at every
    part-leading leaf and `rep` at every replicated leaf. `train` is an
    already-built TrainState spec tree (core/train_plane.py:train_pspecs
    / train_shardings) or None when the training plane is off."""
    from repro.core.state import LayerState, PipelineCarry, TopoState
    from repro.serve.query import QueryState
    topo = TopoState(
        e_src_slot=part, e_dst_slot=part, e_dst_mpart=part, e_dst_mslot=part,
        e_valid=part, r_master_slot=part, r_rep_part=part, r_rep_slot=part,
        r_valid=part, v_exists=part, is_master=part,
        m_part=part, m_slot=part)
    # defer rings are [D * K, W] globally — block-sharded on axis 0 like
    # every part-leading table, so each device carries its own [K, W] ring
    layer = LayerState(
        feat=part, has_feat=part, x_sent=part, has_sent=part, agg=part,
        agg_cnt=part, red_pending=part, red_deadline=part, fwd_pending=part,
        fwd_deadline=part, cms=rep, last_touch=part,
        bc_defer=part, bc_defer_ok=part, rmi_defer=part, rmi_defer_ok=part)
    queries = QueryState(
        qid=part, kind=part, slot=part, part2=part, slot2=part,
        consistent=part, ok=part, issue=part, vec=part, pending=part,
        wire_defer=part, wire_defer_ok=part)
    return PipelineCarry(topo=topo, layers=(layer,) * n_layers, sink=part,
                         sink_seen=part, queries=queries, now=rep, quiet=rep,
                         train=train)


def carry_pspecs(n_layers: int, axis: str = "data", train=None):
    """PartitionSpec tree for PipelineCarry (shard_map in/out specs)."""
    return _carry_tree(n_layers, P(axis), P(), train)


def carry_shardings(mesh: Mesh, n_layers: int, axis: str = "data",
                    train=None):
    """NamedSharding tree for device_put-ing the carry onto the mesh."""
    return _carry_tree(n_layers, NamedSharding(mesh, P(axis)),
                       NamedSharding(mesh, P()), train)


def stats_pspecs(n_layers: int, axis: str = "data"):
    """Per-layer TickStats out-specs: scalars are psum'd inside the tick
    body (replicated), the per-part busy vector concatenates over parts."""
    from repro.core.tick import TickStats
    one = TickStats(broadcast_msgs=P(), reduce_msgs=P(), cross_part_msgs=P(),
                    emitted=P(), dropped=P(), wire_rows=P(),
                    route_deferred=P(), route_dropped=P(),
                    n_suppressed=P(), deliver_rows=P(), occ_bc_defer=P(),
                    occ_rmi_defer=P(), route_peak=P(), outbox_part_peak=P(),
                    busy=P(axis))
    return tuple(one for _ in range(n_layers))


# -------------------------------------- hybrid 2-D ("stage","data") mesh
# Placement of the layer-pipelined carry (ISSUE 7): layer tables are
# STACKED per round with a leading stage axis (round r's leaf holds layer
# r*S+s at stage index s) and sharded over BOTH axes; every other carry
# field keeps its 1-D placement — part arrays shard over "data" and
# replicate per stage (topo/sink/queries are maintained identically on
# every stage), the per-layer CMS shards over "stage" only, and the
# clock/quiet scalars replicate globally (their updates go through
# psum_vote over both axes). The inter-stage ring is stage-sharded on its
# leading axis and data-sharded on its row axis.

def _stage_carry_tree(n_rounds: int, part, part2, stage, rep, ring,
                      train=None):
    """PipelineCarry-shaped tree for the pipelined program: `part2` at
    stacked per-round layer leaves, `stage` at the stacked CMS, `part` at
    stage-replicated part tables, `rep` at scalars, `ring` at stage_ring,
    `train` an already-built stage-replicated TrainState spec tree (the
    training plane uses the same `train_pspecs` as the 1-D mesh — part
    tables shard over "data" and replicate per stage)."""
    from repro.core.state import LayerState, PipelineCarry, TopoState
    from repro.serve.query import QueryState
    topo = TopoState(
        e_src_slot=part, e_dst_slot=part, e_dst_mpart=part, e_dst_mslot=part,
        e_valid=part, r_master_slot=part, r_rep_part=part, r_rep_slot=part,
        r_valid=part, v_exists=part, is_master=part,
        m_part=part, m_slot=part)
    layer = LayerState(
        feat=part2, has_feat=part2, x_sent=part2, has_sent=part2, agg=part2,
        agg_cnt=part2, red_pending=part2, red_deadline=part2,
        fwd_pending=part2, fwd_deadline=part2, cms=stage, last_touch=part2,
        bc_defer=part2, bc_defer_ok=part2, rmi_defer=part2,
        rmi_defer_ok=part2)
    queries = QueryState(
        qid=part, kind=part, slot=part, part2=part, slot2=part,
        consistent=part, ok=part, issue=part, vec=part, pending=part,
        wire_defer=part, wire_defer_ok=part)
    return PipelineCarry(topo=topo, layers=(layer,) * n_rounds, sink=part,
                         sink_seen=part, queries=queries, now=rep, quiet=rep,
                         stage_ring=ring, train=train)


def stage_carry_pspecs(n_rounds: int, stage_axis: str = "stage",
                       axis: str = "data", train=None):
    """PartitionSpec tree for the pipelined PipelineCarry (shard_map
    in/out specs of `_tick_program_2d`)."""
    return _stage_carry_tree(
        n_rounds, P(axis), P(stage_axis, axis), P(stage_axis), P(),
        P(stage_axis, None, axis), train)


def stage_carry_shardings(mesh: Mesh, n_rounds: int,
                          stage_axis: str = "stage", axis: str = "data",
                          train=None):
    """NamedSharding tree for device_put-ing the pipelined carry."""
    ns = lambda spec: NamedSharding(mesh, spec)
    return _stage_carry_tree(
        n_rounds, ns(P(axis)), ns(P(stage_axis, axis)), ns(P(stage_axis)),
        ns(P()), ns(P(stage_axis, None, axis)), train)


def stage_stats_pspecs(n_rounds: int, stage_axis: str = "stage",
                       axis: str = "data"):
    """Per-ROUND TickStats out-specs for the pipelined tick: each stage's
    scalars cover its own layer of the round (data-psum'd only), so they
    leave the shard_map as [1]-shaped leaves stacked to [S] over the
    stage axis; busy leaves as [1, P_loc] stacked to [S, n_parts]. The
    host unstacks layer l = r*S + s from (round r)[s]."""
    from repro.core.tick import TickStats
    s, b = P(stage_axis), P(stage_axis, axis)
    one = TickStats(broadcast_msgs=s, reduce_msgs=s, cross_part_msgs=s,
                    emitted=s, dropped=s, wire_rows=s, route_deferred=s,
                    route_dropped=s, n_suppressed=s, deliver_rows=s,
                    occ_bc_defer=s, occ_rmi_defer=s, route_peak=s,
                    outbox_part_peak=s, busy=b)
    return tuple(one for _ in range(n_rounds))
