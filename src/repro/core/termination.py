"""Termination detection (paper §5.3).

The TerminationCoordinator declares the pipeline quiescent when, for one
full sweep, every layer operator reports (a) no events received since the
last collection and (b) no scheduled timers (window deadlines still
pending). Used to compute bounded-run "runtime" (paper Fig. 4c) and to
flush the pipeline before training (§4.3.1).

Observation runs on the device: `quiet_update` advances a
consecutive-quiet-tick counter INSIDE the super-tick `lax.scan` body, and
the driver reads the resulting counter exactly once per launch
(`TerminationCoordinator.observe_flag`), a one-tick launch included.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.tick import has_work


def moved_msgs(tick_stats):
    """Total MOVEMENT of one layer's TickStats: emissions + reduces +
    broadcasts. This is THE movement vote of `quiet_update`, and the
    training plane's `moved` count.

    TickStats.n_suppressed is deliberately EXCLUDED: a delta-gated
    (suppressed-but-pending) vertex counts as QUIET. Suppression clears
    red_pending without emitting (core/tick.py:round_b_emit), so its
    residual is not in-flight work — it only re-enters on a future touch.
    Counting suppressions as movement would let a stream of sub-eps
    updates hold quiescence off forever and flush() would never
    terminate.
    """
    return tick_stats.emitted + tick_stats.reduce_msgs \
        + tick_stats.broadcast_msgs


def pending_work(layer_states, queries=None, extra_work=None) -> jnp.ndarray:
    """LOCAL in-flight-work count (int32): window timers + the routing
    plane's per-lane defer rings (both via `has_work`) + the query
    plane's wire-lane backlog when a QueryState is given + any
    `extra_work` count the caller carries (the hybrid-parallel pipeline
    passes its inter-stage ring occupancy here, so records in flight
    between stages hold quiescence off exactly like deferred wire rows).

    This is THE single aggregation every quiescence / silence gate uses —
    `quiet_update` (super-tick scan) and the query plane's silence gates
    (serve/query.py:_plane_work). A new carried-work source added here
    reaches all of them at once; added anywhere else it silently weakens
    some gate."""
    timers = jnp.zeros((), jnp.int32)
    for ls in layer_states:
        timers = timers + has_work(ls).astype(jnp.int32)
    if queries is not None:
        timers = timers + jnp.sum(queries.wire_defer_ok.astype(jnp.int32))
    if extra_work is not None:
        timers = timers + jnp.asarray(extra_work, jnp.int32)
    return timers


def quiet_update(quiet: jnp.ndarray, layer_states, tick_stats,
                 router=None, queries=None, extra_work=None) -> jnp.ndarray:
    """One in-graph step of quiescence tracking.

    quiet: int32 scalar — consecutive ticks with no movement and no
    in-flight work (`pending_work`: window timers, routing-plane defer
    rings, the query plane's wire backlog when `queries` is given, plus
    the caller's `extra_work` — e.g. inter-stage ring occupancy).
    Resets to 0 on any emission/reduce/broadcast or pending work.
    Under a sharded tick (`router=MeshRouter`) the pending-work vote is
    globally reduced (`psum_vote`: both mesh axes on a hybrid 2-D mesh)
    so every device agrees on the same counter. On a 1-D mesh the stats
    scalars are already globally reduced by the tick body; on a 2-D mesh
    each stage's scalars cover only ITS layers, so the movement vote is
    additionally psum'd over the stage axis.
    """
    if router is not None and getattr(router, "n_stages", 1) > 1:
        moved_n = jnp.zeros((), jnp.int32)
        for s in tick_stats:
            moved_n = moved_n + moved_msgs(s)
        moved = router.psum_stage(moved_n) > 0
    else:
        moved = jnp.zeros((), bool)
        for s in tick_stats:
            moved = moved | (moved_msgs(s) > 0)
    timers = pending_work(layer_states, queries, extra_work)
    if router is not None:
        timers = router.psum_vote(timers)
    return jnp.where(moved | (timers > 0), jnp.int32(0),
                     quiet + jnp.int32(1))


class TerminationCoordinator:
    def __init__(self, quiet_sweeps: int = 2):
        self.quiet_sweeps = quiet_sweeps
        self._quiet = 0

    @property
    def quiet(self) -> int:
        """Consecutive quiet ticks observed so far (read-only)."""
        return self._quiet

    def seed_quiet(self) -> int:
        """The value to seed a device-resident quiet counter with when
        chaining super-ticks (`run_super_tick(quiet0=...)`): quiescence
        streaks must survive the host round-trip between launches."""
        return self._quiet

    def observe_flag(self, quiet_ticks: int) -> bool:
        """Feed a device-computed consecutive-quiet counter (one host read
        per super-tick). The counter already accumulated within the scan, so
        it replaces — not adds to — the host-side count."""
        self._quiet = int(quiet_ticks)
        return self._quiet >= self.quiet_sweeps

    def reset(self):
        self._quiet = 0
