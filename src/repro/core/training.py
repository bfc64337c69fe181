"""Stale-free distributed training (paper §4.3).

Life-cycle (Fig. 3): StartTraining majority vote -> halt Splitter -> flush
in-flight events (termination detection) -> distributed backprop over the
frozen computation graph -> Alg. 3 model averaging -> phased re-aggregation
and update (Phase 2/3) -> resume streaming.

The layered backward (§4.3.2) mirrors the dataflow in reverse, re-using the
cached aggregator synopses and features from the last forward pass:

  output op : dL/dx^L at masters from the prediction head
  layer l   : recompute h = psi-preactivation from cached (x^l, agg);
              JVPs give dL/dagg and the self-path dL/dx^l;
              per-edge message grads dL/dm_e = dL/dagg_v / cnt_v are
              computed where the edge lives (gather dagg from the dst
              master — the paper ships dagg+agg to replicas, phase 1 step 4)
              and routed back to source masters (phase 2 step 4).

Validated against jax.grad of the static oracle in tests (exact match).

Algorithm 3 (model update): each logical part runs its LOCAL optimizer on
its LOCAL gradients, then parameters are averaged across parts — faithfully
implemented with a vmapped optimizer over the part axis + mean.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import D3Pipeline
from repro.core.state import LayerState, TopoState
from repro.core.train_plane import TrainConfig
from repro.dist.grad_compression import compress_decompress
from repro.nn.layers import Linear


# --------------------------------------------------------------- forward
@partial(jax.jit, static_argnames=("layer",))
def rebuild_layer(layer, params, topo: TopoState, feat, has_feat):
    """Phase 2+3 for one layer: batch reduce (one partial aggregate per
    part/destination) + update + replica broadcast. Returns next-layer
    (feat, has_feat) on the same [P, N] layout, plus (agg, cnt) caches."""
    P, N, d = feat.shape
    pp = jnp.arange(P)[:, None]
    feat_flat = feat.reshape(P * N, d)
    has_flat = has_feat.reshape(P * N)

    src = (pp * N + topo.e_src_slot).reshape(-1)
    live = (topo.e_valid & has_flat[(pp * N + topo.e_src_slot)]).reshape(-1)
    msg = layer.message(params, feat_flat[src])
    tgt = jnp.where(live, (topo.e_dst_mpart * N + topo.e_dst_mslot).reshape(-1),
                    P * N)
    d_agg = msg.shape[-1]
    agg = jnp.zeros((P * N, d_agg)).at[tgt].add(
        jnp.where(live[:, None], msg, 0.0), mode="drop")
    cnt = jnp.zeros((P * N,)).at[tgt].add(live.astype(jnp.float32), mode="drop")

    mean = agg / jnp.maximum(cnt, 1.0)[:, None]
    x_next = layer.update(params, feat_flat, mean)
    is_m = topo.is_master.reshape(P * N)
    ready = is_m & has_flat
    x_next = jnp.where(ready[:, None], x_next, 0.0)

    # replica broadcast of next-layer features
    r_midx = (pp * N + topo.r_master_slot).reshape(-1)
    r_live = topo.r_valid.reshape(-1) & ready[r_midx]
    r_tgt = jnp.where(r_live,
                      (topo.r_rep_part * N + topo.r_rep_slot).reshape(-1), P * N)
    out_d = x_next.shape[-1]
    x_b = x_next.at[r_tgt].set(
        jnp.where(r_live[:, None], x_next[r_midx], 0.0), mode="drop")
    has_next = ready.at[r_tgt].set(r_live, mode="drop")
    return (x_b.reshape(P, N, out_d), has_next.reshape(P, N),
            agg.reshape(P, N, d_agg), cnt.reshape(P, N))


# --------------------------------------------------------------- backward
@partial(jax.jit, static_argnames=("layer",))
def backward_layer(layer, params, topo: TopoState, feat, agg, cnt, g_next):
    """One layer of §4.3.2's two asynchronous phases.

    feat: [P,N,d_in] cached inputs; (agg, cnt): cached synopsis; g_next:
    [P,N,d_out] dL/dx^{l+1} accumulated at masters. Returns (param_grads
    per part [P, ...], g_prev [P,N,d_in] routed to source masters).
    """
    P, N, d_in = feat.shape
    pp = jnp.arange(P)[:, None]
    feat_flat = feat.reshape(P * N, d_in)
    agg_flat = agg.reshape(P * N, -1)
    cnt_flat = cnt.reshape(P * N)
    g_flat = g_next.reshape(P * N, -1)
    mean = agg_flat / jnp.maximum(cnt_flat, 1.0)[:, None]

    # vjp of psi wrt (params, x_self, agg_read); one VJP per part for the
    # per-part parameter gradients of Alg. 3
    def psi_part(p_params, x_p, a_p):
        return layer.update(p_params, x_p, a_p)

    def per_part(x_p, a_p, g_p):
        out, vjp = jax.vjp(lambda q, x, a: psi_part(q, x, a), params, x_p, a_p)
        return vjp(g_p)

    dparams, dx_self, dmean = jax.vmap(per_part)(
        feat_flat.reshape(P, N, d_in), mean.reshape(P, N, -1),
        g_flat.reshape(P, N, -1))
    dx_self = dx_self.reshape(P * N, d_in)
    dmean = dmean.reshape(P * N, -1)
    # d/d agg_sum of mean read
    dagg = dmean / jnp.maximum(cnt_flat, 1.0)[:, None]

    # per-edge message grads: gather dagg at dst master, push through phi
    src = (pp * N + topo.e_src_slot).reshape(-1)
    tgt = (topo.e_dst_mpart * N + topo.e_dst_mslot).reshape(-1)
    live = topo.e_valid.reshape(-1)
    dm = jnp.where(live[:, None], dagg[tgt], 0.0)

    def phi_vjp(x_e, g_e):
        _, vjp = jax.vjp(lambda x: layer.message(params, x), x_e)
        return vjp(g_e)[0]

    dx_src = phi_vjp(feat_flat[src], dm)
    # route to source masters — sources are replicas; their master coords
    # are not stored per edge, so first scatter to the replica coordinate
    # then fold replicas back onto masters via the replication records.
    g_prev = jnp.zeros((P * N, d_in)).at[src].add(
        jnp.where(live[:, None], dx_src, 0.0), mode="drop")
    # replica -> master fold (reverse broadcast)
    r_midx = (pp * N + topo.r_master_slot).reshape(-1)
    r_tgt = (topo.r_rep_part * N + topo.r_rep_slot).reshape(-1)
    r_live = topo.r_valid.reshape(-1)
    fold = jnp.where(r_live[:, None], g_prev[r_tgt], 0.0)
    g_prev = g_prev.at[jnp.where(r_live, r_midx, P * N)].add(fold, mode="drop")
    # zero the replica coordinates (their grad now lives at the master)
    g_prev = g_prev.at[jnp.where(r_live, r_tgt, P * N)].set(0.0, mode="drop")
    # self path lands at the master coordinate directly
    is_m = topo.is_master.reshape(P * N)
    g_prev = g_prev + jnp.where(is_m[:, None], dx_self, 0.0)
    return dparams, g_prev.reshape(P, N, d_in)


# ------------------------------------------------------------ coordinator
@dataclass
class TrainResult:
    losses: list
    votes: int
    flush_ticks: int


class TrainingCoordinator:
    """Majority-vote start, halt+flush, train, rebuild, resume (§4.3.1).

    The halt-flush path — the ONLINE training plane's exactness oracle
    (`core/train_plane.py` golden-tests its quiescent gradients against
    `_full_batch_grads`). Both paths consume the same validated
    `TrainConfig`; switching between them is a config change, not an API
    fork."""

    def __init__(self, pipe: D3Pipeline, head: Linear, head_params,
                 cfg: TrainConfig):
        if not isinstance(cfg, TrainConfig):
            raise TypeError(
                "TrainingCoordinator now takes a TrainConfig (optimizer, "
                "lr, batch_threshold, epochs, compression) instead of "
                f"loose keyword arguments — got {type(cfg).__name__}")
        self.pipe = pipe
        self.head = head
        self.head_params = head_params
        self.cfg = cfg
        self.opt = cfg.optimizer
        self.lr = cfg.lr
        self.batch_threshold = cfg.batch_threshold
        self.labels: dict[int, int] = {}

    def observe_labels(self, labels: dict):
        self.labels.update(labels)

    def votes(self) -> int:
        """Output sub-operators vote StartTraining when their local batch
        reaches the threshold."""
        t = self.pipe.part.t
        per_part = np.zeros(self.pipe.cfg.n_parts, np.int64)
        for vid in self.labels:
            if t.master[vid] >= 0:
                per_part[t.master[vid]] += 1
        return int((per_part >= self.batch_threshold).sum())

    def should_train(self) -> bool:
        return self.votes() > self.pipe.cfg.n_parts // 2

    # ---------------------------------------------------------------- train
    def train(self, epochs: int | None = None) -> TrainResult:
        pipe = self.pipe
        epochs = self.cfg.epochs if epochs is None else epochs
        flush_ticks = pipe.flush()            # stale-free guarantee
        label_arr, label_mask = self._device_labels()

        losses = []
        for _ in range(epochs):
            loss, head_grads, part_grads = self._full_batch_grads(
                label_arr, label_mask)
            losses.append(float(loss))
            self._apply_alg3(head_grads, part_grads)
        self._rebuild()                        # Phases 2 & 3
        return TrainResult(losses=losses, votes=self.votes(),
                           flush_ticks=flush_ticks)

    def _device_labels(self):
        cfg = self.pipe.cfg
        t = self.pipe.part.t
        P, N = cfg.n_parts, cfg.node_cap
        arr = np.zeros((P, N), np.int32)
        mask = np.zeros((P, N), bool)
        for vid, y in self.labels.items():
            p, s = t.master[vid], t.master_slot[vid]
            if p >= 0:
                arr[p, s] = y
                mask[p, s] = True
        return jnp.asarray(arr), jnp.asarray(mask)

    def _full_batch_grads(self, label_arr, label_mask):
        """Loss + per-part grads via the layered backward."""
        pipe = self.pipe
        topo = pipe.topo
        # caches from the quiescent forward state (layer_state unstacks the
        # hybrid engine's per-round stage stacks; identity on a 1-D mesh)
        states = [pipe.layer_state(l) for l in range(len(pipe.layers))]
        feats = [ls.feat for ls in states]
        has = [ls.has_feat for ls in states]
        aggs = [ls.agg for ls in states]
        cnts = [ls.agg_cnt for ls in states]
        x_L = pipe.sink
        seen = pipe.sink_seen

        # output operator: head loss + dL/dx^L (per-part head grads)
        def head_loss(hp, x, y, m):
            logits = self.head(hp, x).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            gold = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
            n = jnp.maximum(jnp.sum(m), 1)
            return jnp.sum(jnp.where(m, -gold, 0.0)) / n

        mask = label_mask & seen
        loss, (head_grads, gx) = jax.value_and_grad(
            lambda hp, x: head_loss(hp, x, label_arr, mask), argnums=(0, 1))(
                self.head_params, x_L)

        part_grads = []
        g = gx
        for li in reversed(range(len(pipe.layers))):
            layer = pipe.layers[li]
            dparams, g = backward_layer(layer, pipe.params[f"l{li}"], topo,
                                        feats[li], aggs[li], cnts[li], g)
            part_grads.append((f"l{li}", dparams))
        return loss, head_grads, dict(part_grads)

    def _apply_alg3(self, head_grads, part_grads):
        """Algorithm 3: local optimizer per part, then parameter mean.
        With cfg.compression, per-part gradients pass through the
        error-feedback compressor first (host-carried residuals)."""
        pipe = self.pipe
        P = pipe.cfg.n_parts
        for name, dparams in part_grads.items():
            if self.cfg.compression:
                if not hasattr(self, "_residuals"):
                    self._residuals = {}
                if name not in self._residuals:
                    self._residuals[name] = jax.tree.map(
                        lambda g: jnp.zeros(g.shape, jnp.float32), dparams)
                dparams, self._residuals[name] = jax.vmap(
                    lambda g, r: compress_decompress(
                        g, r, int8=self.cfg.int8,
                        topk_frac=self.cfg.topk_frac)
                )(dparams, self._residuals[name])
            base = pipe.params[name]
            stacked = jax.tree.map(lambda p: jnp.broadcast_to(p, (P,) + p.shape),
                                   base)
            if not hasattr(self, "_opt_states"):
                self._opt_states = {}
            if name not in self._opt_states:
                self._opt_states[name] = jax.vmap(self.opt.init)(stacked)

            def one(p, g, s):
                upd, s2 = self.opt.update(s, g, p, self.lr)
                return jax.tree.map(lambda a, b: a + b, p, upd), s2

            new_p, new_s = jax.vmap(one)(stacked, dparams,
                                         self._opt_states[name])
            self._opt_states[name] = new_s
            pipe.params[name] = jax.tree.map(lambda x: jnp.mean(x, 0), new_p)
        # head is a single output operator: plain step
        if not hasattr(self, "_head_opt"):
            self._head_opt = self.opt.init(self.head_params)
        upd, self._head_opt = self.opt.update(self._head_opt, head_grads,
                                              self.head_params, self.lr)
        self.head_params = jax.tree.map(lambda a, b: a + b, self.head_params,
                                        upd)

    def _rebuild(self):
        """Phases 2+3: layer-by-layer re-aggregation and update with the
        refreshed model; refreshes the engine caches and the sink."""
        pipe = self.pipe
        feat = pipe.layer_state(0).feat
        has = pipe.layer_state(0).has_feat
        for li, layer in enumerate(pipe.layers):
            nf, nh, agg, cnt = rebuild_layer(layer, pipe.params[f"l{li}"],
                                             pipe.topo, feat, has)
            st = pipe.layer_state(li)
            # x_sent/has_sent get buffers of their own: the next launch
            # donates the carry, and a buffer may be donated only once
            pipe.set_layer_state(li, LayerState(
                feat=feat, has_feat=has, x_sent=jnp.copy(feat),
                has_sent=jnp.copy(has),
                agg=agg, agg_cnt=cnt,
                red_pending=jnp.zeros_like(st.red_pending),
                red_deadline=st.red_deadline,
                fwd_pending=jnp.zeros_like(st.fwd_pending),
                fwd_deadline=st.fwd_deadline, cms=st.cms,
                last_touch=st.last_touch,
                bc_defer=st.bc_defer, bc_defer_ok=st.bc_defer_ok,
                rmi_defer=st.rmi_defer, rmi_defer_ok=st.rmi_defer_ok))
            feat, has = nf, nh
        # masters' final embeddings -> sink
        is_m = pipe.topo.is_master
        pipe.sink = jnp.where(is_m[..., None] & has[..., None], feat, pipe.sink)
        pipe.sink_seen = pipe.sink_seen | (is_m & has)
