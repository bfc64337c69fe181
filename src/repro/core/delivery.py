"""The DELIVERY plane: how routed records land in operator state.

The streaming tick is three planes (ISSUE 3 tentpole):

  COMPUTE  (core/tick.py)   — pure part-local stages that emit
                              part-addressed records;
  ROUTING  (dist/router.py) — a Router moves records to the device that
                              owns their destination part;
  DELIVERY (here)           — a DeliveryBackend lands the delivered
                              records in the local state blocks.

A backend provides the three state effects the tick's hot path needs:

  deliver_set   : feature rows SET at local masters/replicas (Round A
                  inbox apply, Round B broadcast apply) — last-writer-
                  wins plus a touched flag per row;
  deliver_add   : aggregator RMI records ADD (delta vec, delta cnt) at
                  local masters plus a dirty flag (deliver_rmis) — one
                  delivery regardless of the reduce/replace/remove mix;
  agg_read_rows : the MEAN-synopsis read at the forward stage's picked
                  rows (forward_psi).

Two registered implementations, golden-equivalent by test
(tests/test_delivery_backend.py):

  "xla"    — the reference: flat `.at[].set/.add(mode="drop")` scatters
             with the one-past-the-end drop sentinel (state.local_index).
  "pallas" — sorted fixed-capacity segment reductions through
             `kernels/segment_reduce`: each delivery is one stable sort
             plus one one-hot MXU matmul pass (`segment_deliver`), and
             the aggregator read goes through `mean_rows` so the full
             [P*N, d] mean table is never materialized — only the picked
             rows are divided. Off-TPU the kernels run with
             `interpret=True`, which is how CI pins pallas ≡ xla on CPU.

Backends are small frozen dataclasses (hashable) so they ride jit
boundaries as static arguments, exactly like the Routers; both work
unchanged inside `shard_map` (they only ever see the local part block).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from repro.core.aggregators import mean_read
from repro.kernels.segment_reduce.ops import mean_rows, segment_deliver


@dataclass(frozen=True)
class XlaDelivery:
    """Reference backend: XLA scatters guarded by the drop sentinel."""

    name = "xla"

    def deliver_set(self, dst, idx, vals):
        """Set rows of dst [R, d] at idx [C] to vals [C, d]; sentinel rows
        (idx outside [0, R)) drop. Returns (dst', touched [R] bool)."""
        touched = jnp.zeros((dst.shape[0],), bool).at[idx].set(
            True, mode="drop")
        return dst.at[idx].set(vals, mode="drop"), touched

    def deliver_add(self, agg, cnt, idx, vec, dcnt):
        """Add (vec [C, d], dcnt [C]) into (agg [R, d], cnt [R]) at idx.
        Returns (agg', cnt', dirty [R] bool)."""
        live = (idx >= 0) & (idx < agg.shape[0])
        agg = agg.at[idx].add(jnp.where(live[:, None], vec, 0.0),
                              mode="drop")
        cnt = cnt.at[idx].add(dcnt * live, mode="drop")
        dirty = jnp.zeros((agg.shape[0],), bool).at[idx].max(live,
                                                             mode="drop")
        return agg, cnt, dirty

    def agg_read_rows(self, agg, cnt, rows):
        """MEAN synopsis at `rows` [K] (materializes the full mean table,
        then gathers — XLA fuses the division into the gather anyway)."""
        return mean_read(agg, cnt)[rows]


@dataclass(frozen=True)
class PallasDelivery:
    """Pallas backend: sorted segment-reduce deliveries + fused agg read.

    Block sizes default to the MXU-aligned minimum (128) — the streaming
    tick's per-round capacities are hundreds of records, not millions.
    interpret=None resolves per-call to `jax.default_backend() != "tpu"`.
    """

    name = "pallas"
    block_e: int = 128
    block_v: int = 128
    block_r: int = 128
    interpret: Optional[bool] = None

    def deliver_set(self, dst, idx, vals):
        vec_out, _, touched = segment_deliver(
            idx, vals, jnp.zeros((idx.shape[0],), dst.dtype), dst.shape[0],
            mode="set", block_e=self.block_e, block_v=self.block_v,
            interpret=self.interpret)
        return jnp.where(touched[:, None], vec_out, dst), touched

    def deliver_add(self, agg, cnt, idx, vec, dcnt):
        d_vec, d_cnt, dirty = segment_deliver(
            idx, vec, dcnt, agg.shape[0], mode="add", block_e=self.block_e,
            block_v=self.block_v, interpret=self.interpret)
        return agg + d_vec, cnt + d_cnt, dirty

    def agg_read_rows(self, agg, cnt, rows):
        return mean_rows(agg[rows], cnt[rows], block_r=self.block_r,
                         interpret=self.interpret)


BACKENDS = {"xla": XlaDelivery, "pallas": PallasDelivery}


def make_delivery(name: str, **overrides):
    """Build a registered delivery backend (PipelineConfig.delivery_backend
    resolves here); unknown names fail with the registry listed."""
    try:
        cls = BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown delivery_backend {name!r}: expected one of "
            f"{sorted(BACKENDS)}") from None
    return cls(**overrides)
