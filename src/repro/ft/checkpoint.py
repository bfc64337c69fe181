"""Consistent-cut checkpointing (paper §3.2 / §5.1).

Flink uses Chandy-Lamport barrier snapshots that must capture in-flight
iteration-queue events. In the micro-tick engine a tick boundary IS a
consistent cut: all channels are empty between ticks, and what the paper
stores as "in-queue messages" lives in the window-pending state
(red_pending/fwd_pending + deadlines) — so checkpointing the operator
states between ticks captures exactly the same information.

Format: one compressed msgpack blob per checkpoint with raw ndarray
buffers (no pickle — restore-safe), plus host-side partitioner tables.
Compression is zstd when the `zstandard` package is available, else
stdlib zlib; a one-byte codec tag prefixes every blob so either build
restores checkpoints written by the other. Writes go to <step>.tmp then
atomic-rename, so a crash mid-write never corrupts the latest
checkpoint. Async mode hands serialization to a background thread (the
paper's non-blocking snapshots).
"""
from __future__ import annotations

import json
import threading
import warnings
import zlib
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

try:                                    # optional: zstd when installed
    import zstandard
except ImportError:                     # clean env: stdlib fallback
    zstandard = None

# codec tags (format header): every blob starts with one of these bytes.
# \x01/\x02 are the legacy CRC-less formats (restore-only); since ISSUE 10
# writes use \x03/\x04 = tag + CRC32(compressed payload, 4 bytes LE) +
# payload, so a truncated or bit-flipped .ckpt fails loudly at the header
# instead of surfacing a deep zlib/msgpack error.
_CODEC_ZSTD = b"\x01"
_CODEC_ZLIB = b"\x02"
_CODEC_ZSTD_CRC = b"\x03"
_CODEC_ZLIB_CRC = b"\x04"


class CheckpointCorruptError(RuntimeError):
    """A checkpoint blob failed its integrity check (CRC mismatch,
    truncation, or undecodable payload). `CheckpointManager.restore`
    raises it annotated with step + path; step=None restores fall back to
    the previous kept generation with a warning."""


def _compress(raw: bytes) -> bytes:
    if zstandard is not None:
        tag = _CODEC_ZSTD_CRC
        body = zstandard.ZstdCompressor(level=3).compress(raw)
    else:
        tag = _CODEC_ZLIB_CRC
        body = zlib.compress(raw, 6)
    return tag + (zlib.crc32(body) & 0xFFFFFFFF).to_bytes(4, "little") + body


def _decompress(blob: bytes) -> bytes:
    tag = blob[:1]
    if tag in (_CODEC_ZSTD_CRC, _CODEC_ZLIB_CRC):
        if len(blob) < 5:
            raise CheckpointCorruptError(
                "truncated checkpoint: blob ends inside the CRC header")
        want = int.from_bytes(blob[1:5], "little")
        body = blob[5:]
        got = zlib.crc32(body) & 0xFFFFFFFF
        if got != want:
            raise CheckpointCorruptError(
                f"payload CRC mismatch (stored {want:#010x}, computed "
                f"{got:#010x}) — the blob is truncated or bit-flipped")
        if tag == _CODEC_ZSTD_CRC:
            if zstandard is None:
                raise RuntimeError("checkpoint is zstd-compressed but the "
                                   "'zstandard' package is not installed")
            return zstandard.ZstdDecompressor().decompress(body)
        return zlib.decompress(body)
    if tag == _CODEC_ZSTD:
        if zstandard is None:
            raise RuntimeError("checkpoint is zstd-compressed but the "
                               "'zstandard' package is not installed")
        return zstandard.ZstdDecompressor().decompress(blob[1:])
    if tag == _CODEC_ZLIB:
        return zlib.decompress(blob[1:])
    if blob[:4] == b"\x28\xb5\x2f\xfd":
        # legacy checkpoint from before the codec tag: a bare zstd frame
        if zstandard is None:
            raise RuntimeError("legacy zstd checkpoint needs the "
                               "'zstandard' package to restore")
        return zstandard.ZstdDecompressor().decompress(blob)
    raise CheckpointCorruptError(f"unknown checkpoint codec tag {tag!r}")


def _pack_tree(tree) -> bytes:
    leaves, treedef = jax.tree.flatten(tree)
    payload = {
        "treedef": str(treedef),
        "leaves": [
            {"dtype": str(np.asarray(l).dtype), "shape": list(np.asarray(l).shape),
             "data": np.ascontiguousarray(np.asarray(l)).tobytes()}
            for l in leaves
        ],
    }
    return _compress(msgpack.packb(payload, use_bin_type=True))


def _unpack_leaves(blob: bytes):
    payload = msgpack.unpackb(_decompress(blob), raw=False)
    # .copy(): frombuffer views are read-only; host tables are mutated live
    return [np.frombuffer(l["data"], dtype=np.dtype(l["dtype"])).reshape(
        l["shape"]).copy() for l in payload["leaves"]]


@dataclass
class CheckpointInfo:
    step: int
    path: Path


class CheckpointManager:
    def __init__(self, directory, keep: int = 3, async_write: bool = False):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self.async_write = async_write
        self._pending: list[threading.Thread] = []

    # ------------------------------------------------------------ generic
    def save(self, step: int, tree, meta: dict | None = None,
             aux: dict | None = None):
        """Checkpoint any pytree (params, optimizer state, engine states).

        `aux` is a flat {name: ndarray} dict of variable-shape host tables
        restored as-is (no template check)."""
        tree = jax.tree.map(np.asarray, tree)   # device -> host snapshot NOW
        aux = None if aux is None else {k: np.asarray(v)
                                        for k, v in aux.items()}

        def _write():
            blob = _pack_tree(tree)
            tmp = self.dir / f"{step:010d}.ckpt.tmp"
            final = self.dir / f"{step:010d}.ckpt"
            tmp.write_bytes(blob)
            if aux is not None:
                names = sorted(aux)
                (self.dir / f"{step:010d}.aux").write_bytes(
                    _pack_tree([aux[k] for k in names]))
                (self.dir / f"{step:010d}.auxnames.json").write_text(
                    json.dumps(names))
            if meta is not None:
                (self.dir / f"{step:010d}.meta.json").write_text(
                    json.dumps(meta))
            tmp.rename(final)
            self._gc()

        if self.async_write:
            t = threading.Thread(target=_write, daemon=True)
            t.start()
            self._pending.append(t)
        else:
            _write()

    def wait(self):
        for t in self._pending:
            t.join()
        self._pending.clear()

    def _load_leaves(self, info: CheckpointInfo):
        """Decode one blob; any integrity failure surfaces as a
        CheckpointCorruptError carrying step + path."""
        try:
            return _unpack_leaves(info.path.read_bytes())
        except CheckpointCorruptError as e:
            raise CheckpointCorruptError(
                f"corrupt checkpoint at step {info.step} "
                f"({info.path}): {e}") from e
        except Exception as e:   # zlib.error / msgpack / struct depths
            raise CheckpointCorruptError(
                f"corrupt checkpoint at step {info.step} ({info.path}): "
                f"{type(e).__name__}: {e}") from e

    def checkpoints(self) -> list[CheckpointInfo]:
        return [CheckpointInfo(int(p.stem.split(".")[0]), p)
                for p in sorted(self.dir.glob("*.ckpt"))]

    def restore(self, template, step: int | None = None):
        """Restore into the structure of `template` (shape/dtype checked).

        step=None restores the newest checkpoint; if its blob fails the
        integrity check the restore FALLS BACK to the previous kept
        generation (newest -> oldest) with a warning — a torn write never
        strands recovery while an older consistent cut exists. An
        explicit step raises CheckpointCorruptError instead."""
        infos = ([CheckpointInfo(step, self.dir / f"{step:010d}.ckpt")]
                 if step is not None else list(reversed(self.checkpoints())))
        if not infos:
            raise FileNotFoundError(f"no checkpoint in {self.dir}")
        errors: list[CheckpointCorruptError] = []
        for info in infos:
            try:
                leaves = self._load_leaves(info)
            except CheckpointCorruptError as e:
                if step is not None:
                    raise
                errors.append(e)
                warnings.warn(f"{e} — falling back to the previous kept "
                              "generation")
                continue
            t_leaves, treedef = jax.tree.flatten(template)
            assert len(leaves) == len(t_leaves), \
                f"checkpoint has {len(leaves)} leaves, template {len(t_leaves)}"
            out = []
            for got, want in zip(leaves, t_leaves):
                w = np.asarray(want)
                assert tuple(got.shape) == tuple(w.shape), (got.shape, w.shape)
                out.append(jnp.asarray(got.astype(w.dtype)))
            return jax.tree.unflatten(treedef, out), info.step
        raise errors[0]

    def restore_aux(self, step: int | None = None) -> dict:
        info = self.latest() if step is None else CheckpointInfo(
            step, self.dir / f"{step:010d}.ckpt")
        names = json.loads(
            (self.dir / f"{info.step:010d}.auxnames.json").read_text())
        leaves = _unpack_leaves(
            (self.dir / f"{info.step:010d}.aux").read_bytes())
        return dict(zip(names, leaves))

    def latest(self) -> CheckpointInfo | None:
        ckpts = sorted(self.dir.glob("*.ckpt"))
        if not ckpts:
            return None
        p = ckpts[-1]
        return CheckpointInfo(int(p.stem.split(".")[0]), p)

    def _gc(self):
        ckpts = sorted(self.dir.glob("*.ckpt"))
        for p in ckpts[: -self.keep]:
            p.unlink(missing_ok=True)
            meta = p.with_suffix("").with_suffix(".meta.json")
            meta.unlink(missing_ok=True)

    # ----------------------------------------------------------- pipeline
    def save_pipeline(self, step: int, pipe):
        """Full engine snapshot: device state + host partitioner tables +
        metrics. Window-pending state (the in-flight events) is inside
        LayerState and held point queries live in the QueryState table,
        so this IS the Chandy-Lamport-equivalent cut — a restored carry
        answers pending `consistent` queries identically."""
        pipe.settle()
        t = pipe.part.t
        aux = {
            "degree": t.degree, "replicas": t.replicas, "load": t.load,
            "master": t.master, "master_slot": t.master_slot,
            "next_vslot": t.next_vslot, "next_eslot": t.next_eslot,
            "repl_counters": pipe.part._repl_counters,
            "slot_keys": np.asarray([[p, v] for (p, v) in t.slot_of],
                                    np.int64).reshape(-1, 2),
            "slot_vals": np.asarray(list(t.slot_of.values()), np.int64),
            "now": np.asarray(pipe.now),
        }
        tree = {"topo": pipe.topo, "layers": pipe.states, "sink": pipe.sink,
                "sink_seen": pipe.sink_seen, "queries": pipe.queries,
                "params": pipe.params,
                # hybrid-parallel pipelines DO have a non-empty channel at
                # the tick cut: the inter-stage ring's in-flight rows ride
                # the snapshot (None on a 1-D mesh — zero leaves)
                "stage_ring": getattr(pipe, "stage_ring", None),
                # training-plane state (labels/dirty window, live params,
                # optimizer + error-feedback residuals) is part of the
                # consistent cut; None when cfg.train_cap == 0
                "train": getattr(pipe, "train_state", None)}
        self.save(step, tree, meta={"now": pipe.now}, aux=aux)

    def restore_pipeline(self, pipe, step: int | None = None) -> int:
        pipe.settle()
        template = {"topo": pipe.topo, "layers": pipe.states,
                    "sink": pipe.sink, "sink_seen": pipe.sink_seen,
                    "queries": pipe.queries, "params": pipe.params,
                    "stage_ring": getattr(pipe, "stage_ring", None),
                    "train": getattr(pipe, "train_state", None)}
        tree, got_step = self.restore(template, step)
        pipe.topo = tree["topo"]
        pipe.states = tree["layers"]
        pipe.sink = tree["sink"]
        pipe.sink_seen = tree["sink_seen"]
        pipe.queries = tree["queries"]
        pipe.params = tree["params"]
        if tree.get("stage_ring") is not None:
            pipe.stage_ring = tree["stage_ring"]
        if tree.get("train") is not None:
            pipe.train_state = tree["train"]
            if hasattr(pipe, "_sync_params_from_train"):
                pipe._sync_params_from_train()
        h = self.restore_aux(got_step)
        t = pipe.part.t
        t.degree = np.asarray(h["degree"])
        t.replicas = np.asarray(h["replicas"])
        t.load = np.asarray(h["load"])
        t.master = np.asarray(h["master"])
        t.master_slot = np.asarray(h["master_slot"])
        t.next_vslot = np.asarray(h["next_vslot"])
        t.next_eslot = np.asarray(h["next_eslot"])
        pipe.part._repl_counters = np.asarray(h["repl_counters"])
        keys = np.asarray(h["slot_keys"]).reshape(-1, 2)
        vals = np.asarray(h["slot_vals"])
        t.slot_of = {(int(p), int(v)): int(s)
                     for (p, v), s in zip(keys, vals)}
        pipe.now = int(np.asarray(h["now"]))
        # the restored table may hold queries: launches sync at once
        # until one reports it empty
        pipe._queries_held = True
        return got_step
