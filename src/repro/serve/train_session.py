"""Host-side online-training loop: interleave graph updates and labels.

`TrainSession` is the training-plane twin of `ServeSession`: it wraps a
training-enabled `D3Pipeline` (cfg.train_cap > 0 + a `TrainConfig`) and
drives its super-tick launches with label admissions aboard:
`advance_super` stages T update micro-ticks and spreads the queued labels
over them, so the windowed online training step (fire-masked backprop +
Algorithm 3) runs inside the same device launch as the update stream —
still ONE host sync per super-tick. `advance` is the one-tick launch
(queued labels admit in the very next micro-tick); `step` and `flush`
launch `super_ticks` ticks at a time.

Labels queue host-side until a tick has budget (`capacities().train_cap`
per tick); vids the partitioner has never seen are silently dropped at
admission (there is no master slot to label). Training progress — loss,
gradient norm, fired steps — is read on demand via `train_stats()`,
which adds the host-side label backlog. Unlike the halt-flush
`TrainingCoordinator` (core/training.py), nothing here stops the stream.
"""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class TrainSession:
    pipe: object                                 # a training-enabled D3Pipeline
    super_ticks: int = 8                         # T per device launch
                                                 # (1 = a tick a launch)
    _queue: list = field(default_factory=list)   # un-admitted (vid, gold)

    def __post_init__(self):
        if getattr(self.pipe, "train_cfg", None) is None:
            raise ValueError(
                "TrainSession needs a training-enabled pipeline: set "
                "PipelineConfig.train_cap > 0 and pass "
                "D3Pipeline(..., train=TrainConfig(...)) (the training "
                "plane is compiled away at train_cap=0)")
        if self.super_ticks < 1:
            raise ValueError(f"super_ticks={self.super_ticks} must be >= 1 "
                             "(1 = one micro-tick a launch)")

    # ------------------------------------------------------------- labels
    def observe_labels(self, labels):
        """Enqueue ground-truth labels: {vid: gold_class} or
        [(vid, gold_class), ...]. They admit into the device-side sliding
        window on the next advance, oldest first."""
        pairs = labels.items() if isinstance(labels, dict) else labels
        for vid, y in pairs:
            self._queue.append((int(vid), int(y)))

    # ------------------------------------------------------------ advance
    def advance(self, edges=None, feats=None, window=None):
        """One micro-tick: `advance_super` at T=1, read at once. Queued
        labels admit now, up to the per-tick label budget (the rest stay
        queued). Returns the tick's per-layer TickStats."""
        return list(self.advance_super(
            [edges] if edges is not None else None,
            [feats] if feats is not None else None, T=1, window=window)[0])

    def advance_super(self, edge_chunks=None, feat_chunks=None,
                      T=None, window=None, quiet0: int = 0):
        """One super-tick: queued labels spread over the launch's T
        micro-ticks (earliest first, at most `capacities().train_cap` per
        tick), interleaving label ingest with the update stream on
        device. Labels beyond the launch's
        admission budget stay queued — they never overflow a tick's
        fixed-capacity label batch."""
        edge_chunks = list(edge_chunks) if edge_chunks is not None else []
        feat_chunks = list(feat_chunks) if feat_chunks is not None else []
        n = max(len(edge_chunks), len(feat_chunks), 1)
        T = int(T) if T is not None else n
        per_tick = self.pipe.cfg.capacities().train_cap
        l, self._queue = self._queue[:per_tick * T], self._queue[per_tick * T:]
        l_chunks = [l[i * per_tick: (i + 1) * per_tick] for i in range(T)]
        return self.pipe.run_super_tick(edge_chunks, feat_chunks, T=T,
                                        window=window, quiet0=quiet0,
                                        label_chunks=l_chunks)

    def step(self, edges=None, feats=None, **kw):
        """One launch of `super_ticks` micro-ticks, the first carrying
        `edges` and `feats`."""
        e = [edges] if edges is not None else None
        f = [feats] if feats is not None else None
        return self.advance_super(e, f, T=self.super_ticks, **kw)

    def flush(self, max_ticks: int = 128):
        """Drain the pipeline, `super_ticks` ticks a launch: the label
        backlog admits first (labels only enter with tick budget), then
        the normal flush runs until device quiescence — so the final fire
        at the quiescent fixed point sees every label submitted so far."""
        ran = 0
        while self._queue and ran < max_ticks:
            self.advance_super(T=self.super_ticks)
            ran += self.super_ticks
        remaining = max(max_ticks - ran, 8)
        return ran + self.pipe.flush_super(max_ticks=remaining,
                                           T=self.super_ticks)

    # ------------------------------------------------------------ results
    @property
    def backlog(self) -> int:
        """Labels submitted but not yet admitted on device."""
        return len(self._queue)

    def train_stats(self) -> dict:
        """Device training diagnostics (one host sync) + label backlog."""
        out = dict(self.pipe.train_stats())
        out["backlog"] = self.backlog
        return out
