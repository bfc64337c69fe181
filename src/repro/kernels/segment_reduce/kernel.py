"""Pallas TPU kernel: segment-sum of sorted messages via one-hot MXU matmul.

Layout contract (prepared by ops.py):
  * messages [E_pad, d] sorted by destination, padded so that no BLOCK_E
    edge block spans two BLOCK_V output blocks;
  * seg_local [E_pad] — destination index *within* its output block
    (BLOCK_V sentinel = padding row, contributes nothing). The kernel
    views it as [n_eblk, 1, BLOCK_E] so each grid step reads one
    lane-dense row of ids: a 1-D [BLOCK_E] block is refused by Mosaic
    for any BLOCK_E below XLA's 1024-element tiling of 1-D int arrays;
  * eblk_to_vblk [n_eblk] (scalar-prefetch) — which output tile each edge
    block accumulates into (non-decreasing);
  * first_visit [n_eblk] (scalar-prefetch) — 1 where this edge block is the
    first to touch its output tile (zero-initialize then).

Grid is 1-D over edge blocks; the output BlockSpec's index_map reads the
scalar-prefetched eblk_to_vblk, so consecutive grid steps can revisit the
same output tile and accumulate in VMEM (the standard TPU reduction
pattern). The inner op is onehot @ msgs — a (BLOCK_V x BLOCK_E) x
(BLOCK_E x d) matmul on the MXU at HIGHEST precision, so the 0/1
selection reproduces every f32 message exactly (the default precision
would round them to bf16).

VMEM budget per step: BLOCK_E*d (msgs) + BLOCK_V*d (out tile) + BLOCK_E
(ids) floats. Defaults BLOCK_E=512, BLOCK_V=256, d<=1024 stay well under
16 MB VMEM with MXU-aligned (multiple-of-128) matmul dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_E = 512
DEFAULT_BLOCK_V = 256


def _kernel(eblk_to_vblk, first_visit,      # scalar prefetch
            seg_ref, msg_ref, out_ref, *, block_v: int):
    i = pl.program_id(0)

    @pl.when(first_visit[i] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]                                  # [1, BLOCK_E]
    msgs = msg_ref[...]                                 # [BLOCK_E, d]
    # one-hot [BLOCK_V, BLOCK_E]; padding rows (seg == block_v) select none
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_v, seg.shape[1]), 0)
    onehot = (rows == seg).astype(msgs.dtype)
    out_ref[...] += jax.lax.dot_general(
        onehot, msgs, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=out_ref.dtype)


DEFAULT_BLOCK_R = 128


@functools.partial(jax.jit, static_argnames=("n_vblocks", "block_e",
                                             "block_v", "interpret"))
def segment_sum_kernel(msgs, seg_local, eblk_to_vblk, first_visit,
                       n_vblocks: int, block_e: int = DEFAULT_BLOCK_E,
                       block_v: int = DEFAULT_BLOCK_V,
                       interpret: bool = True):
    """msgs [E_pad, d] (sorted/padded), returns [n_vblocks*block_v, d]."""
    e_pad, d = msgs.shape
    n_eblk = e_pad // block_e
    assert n_eblk * block_e == e_pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_eblk,),
        in_specs=[
            pl.BlockSpec((None, 1, block_e), lambda i, ev, fv: (i, 0, 0)),
            pl.BlockSpec((block_e, d), lambda i, ev, fv: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda i, ev, fv: (ev[i], 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_v=block_v),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_vblocks * block_v, d), msgs.dtype),
        interpret=interpret,
    )(eblk_to_vblk, first_visit, seg_local.reshape(n_eblk, 1, block_e),
      msgs)


def _mean_rows_kernel(sum_ref, cnt_ref, out_ref):
    # counts <= 0 (neighborhood emptied by remove/replace RMIs) read zero,
    # not the stale sigma/1 residual — same contract as
    # core/aggregators.mean_read and ref.rmi_apply_read_ref
    cnt = cnt_ref[...]
    out_ref[...] = jnp.where(cnt > 0,
                             sum_ref[...] / jnp.maximum(cnt, 1.0), 0.0)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def mean_rows_kernel(sums, cnts, block_r: int = DEFAULT_BLOCK_R,
                     interpret: bool = True):
    """Row-wise synopsis read: sums [K_pad, d] / max(cnts [K_pad, 1], 1).

    The VPU half of the fused RMI-apply + read: the caller gathers the
    picked aggregator rows and this kernel divides them by their counts,
    so the full [P*N, d] mean table is never materialized. K_pad must be
    a multiple of block_r (ops.py pads; padded counts are 1). The [*, 1]
    count block is lane-sub-tile: fine in interpret mode, padded to the
    (8, 128) f32 tile by Mosaic on real TPUs.
    """
    k_pad, d = sums.shape
    assert k_pad % block_r == 0 and cnts.shape == (k_pad, 1)
    return pl.pallas_call(
        _mean_rows_kernel,
        grid=(k_pad // block_r,),
        in_specs=[pl.BlockSpec((block_r, d), lambda i: (i, 0)),
                  pl.BlockSpec((block_r, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k_pad, d), sums.dtype),
        interpret=interpret,
    )(sums, cnts)
