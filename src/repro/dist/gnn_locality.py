"""Vertex-cut locality plan + shard_map GNN train step (§Perf cell A).

The D3-GNN idea applied to full-graph training: block-partition vertices
over shards, place every edge on its RECEIVER's shard, and materialize the
senders each shard does not own as halo rows fed by a per-layer all_to_all
exchange. Aggregations then stay shard-local (receivers are always owned),
so the only wire traffic is the halo feature rows — the same
master/replica broadcast structure the streaming engine uses, frozen into
a static plan.

`build_plan` is host-side numpy: it returns padded [S, ...] arrays ready
to reshape into shard_map operands. `make_locality_train_step` returns a
jittable (params, opt_state, batch) -> (params', opt_state', loss) whose
gradients equal the global single-device step (tested on a forced
8-device CPU mesh).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.graph import segment
from repro.graph.graphs import Graph
from repro.optim import adam, apply_updates, clip_by_global_norm


@dataclass
class LocalityPlan:
    """Static routing tables for one graph snapshot.

    Local sender index space per shard: rows [0, n_loc) are owned vertices,
    row n_loc + p * r_cap + r is halo slot r received from shard p.
    """
    n_loc: int                     # owned vertices per shard
    r_cap: int                     # halo rows per (src, dst) shard pair
    senders_local: np.ndarray      # [S, E_cap] int32 into the local buffer
    receivers_local: np.ndarray    # [S, E_cap] int32, < n_loc (owned)
    edge_mask: np.ndarray          # [S, E_cap] bool
    send_idx: np.ndarray           # [S, S, r_cap] int32 owned rows to ship
    send_mask: np.ndarray          # [S, S, r_cap] bool


def build_plan(senders, receivers, n_nodes: int, n_shards: int,
               e_cap: int | None = None,
               r_cap: int | None = None) -> LocalityPlan:
    """Place each edge on its receiver's shard; dedupe halo senders."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    S = n_shards
    assert n_nodes % S == 0, f"{n_nodes} nodes not divisible by {S} shards"
    n_loc = n_nodes // S
    owner = lambda v: v // n_loc
    local = lambda v: v % n_loc

    shard_edges = [[] for _ in range(S)]           # (sender_local, recv_local)
    halo = [[dict() for _ in range(S)] for _ in range(S)]  # [src][dst] {lu: r}
    for u, v in zip(senders, receivers):
        s = int(owner(v))
        if owner(u) == s:
            su = int(local(u))
        else:
            p = int(owner(u))
            table = halo[p][s]
            r = table.setdefault(int(local(u)), len(table))
            su = None              # resolved after r_cap is known
            shard_edges[s].append((p, int(local(u)), int(local(v))))
            continue
        shard_edges[s].append((-1, su, int(local(v))))

    if r_cap is None:
        r_cap = max((len(halo[p][q]) for p in range(S) for q in range(S)),
                    default=0)
        r_cap = max(r_cap, 1)
    if e_cap is None:
        e_cap = max(max((len(e) for e in shard_edges), default=0), 1)

    send_idx = np.zeros((S, S, r_cap), np.int32)
    send_mask = np.zeros((S, S, r_cap), bool)
    for p in range(S):
        for q in range(S):
            for lu, r in halo[p][q].items():
                assert r < r_cap, f"halo overflow: pair ({p},{q}) needs {r + 1} > r_cap={r_cap}"
                send_idx[p, q, r] = lu
                send_mask[p, q, r] = True

    senders_local = np.zeros((S, e_cap), np.int32)
    receivers_local = np.zeros((S, e_cap), np.int32)
    edge_mask = np.zeros((S, e_cap), bool)
    for s in range(S):
        assert len(shard_edges[s]) <= e_cap, \
            f"shard {s} has {len(shard_edges[s])} edges > e_cap={e_cap}"
        for i, (p, lu, lv) in enumerate(shard_edges[s]):
            if p < 0:
                senders_local[s, i] = lu
            else:
                senders_local[s, i] = n_loc + p * r_cap + halo[p][s][lu]
            receivers_local[s, i] = lv
            edge_mask[s, i] = True
    return LocalityPlan(n_loc=n_loc, r_cap=r_cap,
                        senders_local=senders_local,
                        receivers_local=receivers_local,
                        edge_mask=edge_mask,
                        send_idx=send_idx, send_mask=send_mask)


def _halo_exchange(x_own, send_idx, send_mask, axis_name):
    """all_to_all the owned rows each peer needs; [S * r_cap, d] halo."""
    S, r_cap = send_idx.shape
    buf = jnp.where(send_mask[:, :, None], x_own[send_idx], 0)   # [S,r_cap,d]
    recv = lax.all_to_all(buf.reshape(S * r_cap, -1), axis_name,
                          split_axis=0, concat_axis=0, tiled=True)
    return recv


def _pna_local_update(layer, lparams, x_full, senders, receivers, edge_mask,
                      n_own):
    """PNA layer with the post-MLP restricted to OWNED rows (halo rows only
    feed messages) — removes the |halo|/|owned| overcompute of running the
    full layer and slicing."""
    x_own = x_full[:n_own]
    m = layer.pre(lparams["pre"],
                  jnp.concatenate([x_full[senders], x_full[receivers]], -1))
    aggs = jnp.concatenate([
        segment.segment_mean(m, receivers, n_own, edge_mask),
        segment.segment_max(m, receivers, n_own, edge_mask),
        segment.segment_min(m, receivers, n_own, edge_mask),
        segment.segment_std(m, receivers, n_own, edge_mask),
    ], axis=-1)
    deg = segment.segment_count(receivers, n_own, edge_mask)
    logd = jnp.log(deg + 1.0)
    amp = (logd / layer.avg_log_deg)[:, None]
    att = (layer.avg_log_deg / jnp.maximum(logd, 1e-6))[:, None]
    scaled = jnp.concatenate([aggs, aggs * amp, aggs * att], axis=-1)
    h = layer.post(lparams["post"], jnp.concatenate([x_own, scaled], -1))
    return jax.nn.relu(h) if layer.act else h


def make_locality_train_step(model, n_classes: int, axes, mesh,
                             local_update: bool = False,
                             compute_dtype=None, lr: float = 1e-3,
                             clip: float = 1.0):
    """(params, opt_state, batch) -> (params', opt_state', loss).

    batch (leading dim S, sharded over `axes`):
      x [S, n_loc, d], labels [S, n_loc], label_mask [S, n_loc],
      senders/receivers/edge_mask [S, E_cap],
      send_idx/send_mask [S, S, r_cap].
    Gradients are psum'd and the update applied replicated, so the result
    is bit-comparable to the global-graph step.
    """
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    ax = axes_t if len(axes_t) > 1 else axes_t[0]
    opt = adam()

    def local_ce_sum(params, b):
        x = b["x"]
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        n_own = x.shape[0]
        for i, layer in enumerate(model.layers):
            halo = _halo_exchange(x, b["send_idx"], b["send_mask"], ax)
            x_full = jnp.concatenate([x, halo.astype(x.dtype)], axis=0)
            if local_update and hasattr(layer, "pre"):
                x = _pna_local_update(layer, params[f"l{i}"], x_full,
                                      b["senders"], b["receivers"],
                                      b["edge_mask"], n_own)
            else:
                g = Graph(senders=b["senders"], receivers=b["receivers"],
                          x=x_full, edge_mask=b["edge_mask"])
                x = layer(params[f"l{i}"], g, x_full)[:n_own]
        logits = model.head(params["head"], x) if n_classes else x
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logp, b["labels"][:, None], -1)[:, 0]
        return jnp.sum(jnp.where(b["label_mask"], -gold, 0.0))

    def shard_body(params, batch):
        b = jax.tree.map(lambda a: a[0], batch)      # strip the S-block dim
        ce_sum, grads = jax.value_and_grad(local_ce_sum)(params, b)
        cnt = lax.psum(jnp.sum(b["label_mask"].astype(jnp.float32)), ax)
        cnt = jnp.maximum(cnt, 1.0)
        loss = lax.psum(ce_sum, ax) / cnt
        grads = jax.tree.map(lambda g: lax.psum(g.astype(jnp.float32), ax)
                             / cnt, grads)
        return loss, grads

    batch_keys = ("x", "labels", "label_mask", "senders", "receivers",
                  "edge_mask", "send_idx", "send_mask")
    in_batch_specs = {k: P(axes_t) for k in batch_keys}
    sharded = jax.shard_map(shard_body, mesh=mesh,
                            in_specs=(P(), in_batch_specs),
                            out_specs=(P(), P()), check_vma=False)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = sharded(params, {k: batch[k] for k in batch_keys})
        grads, _ = clip_by_global_norm(grads, clip)
        updates, new_opt = opt.update(opt_state, grads, params, lr)
        return apply_updates(params, updates), new_opt, loss

    return step
