"""Streaming vertex-cut partitioner invariants + Alg. 5 properties."""
import numpy as np
import pytest

# hypothesis is an optional [test] extra: the property tests below are only
# defined when it is importable; the deterministic tests always run
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core.explosion import (imbalance_factor, layer_parallelisms,
                                  physical_busy, physical_part)
from repro.core.partitioner import StreamingPartitioner
from repro.graph.graphs import powerlaw_edges


@pytest.mark.parametrize("method", ["hdrf", "clda", "random"])
def test_partitioner_invariants(method):
    rng = np.random.default_rng(0)
    edges = powerlaw_edges(rng, 200, 1000)
    part = StreamingPartitioner(8, 200, method=method)
    e_rows, r_rows, v_rows = part.ingest_edges(edges)
    # every edge assigned exactly once
    assert len(e_rows["part"]) == len(edges)
    assert (e_rows["part"] >= 0).all() and (e_rows["part"] < 8).all()
    # masters unique & stable
    t = part.t
    seen = t.master >= 0
    assert seen.sum() == len(np.unique(edges))
    # replication factor >= 1 and every replica row points at a real master
    assert part.replication_factor() >= 1.0
    for mp, ms in zip(r_rows["part"], r_rows["master_slot"]):
        assert 0 <= mp < 8
    # edge slots unique per part
    for p in range(8):
        slots = e_rows["edge_slot"][e_rows["part"] == p]
        assert len(slots) == len(set(slots.tolist()))


def test_hdrf_beats_random_on_replication():
    """Paper §6: HDRF/CLDA surpass Random on communication metrics; the
    driver of that is the replication factor."""
    rng = np.random.default_rng(1)
    edges = powerlaw_edges(rng, 300, 3000)
    rf = {}
    for method in ("hdrf", "clda", "random"):
        p = StreamingPartitioner(8, 300, method=method)
        p.ingest_edges(edges)
        rf[method] = p.replication_factor()
    assert rf["hdrf"] < rf["random"]
    assert rf["clda"] < rf["random"]


def test_hdrf_balance():
    rng = np.random.default_rng(2)
    edges = powerlaw_edges(rng, 300, 3000)
    p = StreamingPartitioner(8, 300, method="hdrf")
    p.ingest_edges(edges)
    assert p.load_imbalance() < 1.5


# ------------------------------------------------------------- Algorithm 5
if HAS_HYPOTHESIS:
    @given(st.integers(0, 10_000), st.integers(1, 64))
    @settings(max_examples=200, deadline=None)
    def test_alg5_physical_in_range(logical, par):
        max_par = 64
        phys = physical_part(logical, par, max_par)
        assert 0 <= phys < par

    @given(st.integers(1, 64))
    @settings(max_examples=64, deadline=None)
    def test_alg5_no_idle_operator(par):
        """Paper: 'Each operator is assigned at least one key'."""
        max_par = 64
        phys = physical_part(np.arange(max_par), par, max_par)
        assert set(phys.tolist()) == set(range(par))
else:
    @pytest.mark.skip(reason="property tests need the optional [test] extra")
    def test_alg5_properties():
        pytest.importorskip("hypothesis")


def test_alg5_contiguity_and_rescale():
    max_par = 32
    logical = np.arange(max_par)
    p8 = physical_part(logical, 8, max_par)
    # contiguous key ranges (monotone non-decreasing)
    assert (np.diff(p8) >= 0).all()
    # rescale 8 -> 16: each logical part maps deterministically, no state
    # exchange outside the part granularity
    p16 = physical_part(logical, 16, max_par)
    assert (np.diff(p16) >= 0).all()
    assert len(set(p16.tolist())) == 16


def test_explosion_parallelisms():
    pars = layer_parallelisms(4, 3.0, 3, max_parallelism=256)
    assert pars == [4, 12, 36]
    pars_capped = layer_parallelisms(64, 3.0, 3, max_parallelism=128)
    assert pars_capped[-1] == 128


def test_physical_busy_aggregation():
    busy = np.arange(8, dtype=np.int64)
    agg = physical_busy(busy, 4, 8)
    assert agg.sum() == busy.sum()
    assert imbalance_factor(np.array([2.0, 2.0])) == 1.0


@pytest.mark.parametrize("cap", ["node_cap", "edge_cap", "repl_cap"])
def test_slot_overflow_raises_naming_part_and_cap(cap):
    """An allocation past a per-part cap fails on the host, naming the
    part and the cap — on the device the slot would spill into the next
    part's rows (or be dropped for the last part)."""
    rng = np.random.default_rng(0)
    edges = powerlaw_edges(rng, 200, 1000)
    roomy = dict(node_cap=1000, edge_cap=1000, repl_cap=1000)
    roomy[cap] = 4
    part = StreamingPartitioner(8, 200, **roomy)
    with pytest.raises(RuntimeError, match=rf"part \d+ .*{cap}=4"):
        part.ingest_edges(edges)


def test_caps_admit_exactly_their_slots():
    """A cap is a budget, not an off-by-one: a stream that needs exactly
    cap slots in some part ingests cleanly."""
    rng = np.random.default_rng(0)
    edges = powerlaw_edges(rng, 200, 1000)
    free = StreamingPartitioner(8, 200)
    free.ingest_edges(edges)
    t = free.t
    tight = StreamingPartitioner(
        8, 200, node_cap=int(t.next_vslot.max()),
        edge_cap=int(t.next_eslot.max()),
        repl_cap=int(free._repl_counters.max()))
    tight.ingest_edges(edges)
    assert (tight.t.next_vslot == t.next_vslot).all()


def test_pipeline_passes_caps_to_partitioner():
    """D3Pipeline hands its PipelineConfig caps to the partitioner, so a
    stream that outgrows node_cap stops at the tick that would overflow."""
    import jax

    from repro.core.pipeline import D3Pipeline, PipelineConfig
    from repro.graph.sage import GraphSAGE

    rng = np.random.default_rng(0)
    edges = powerlaw_edges(rng, 200, 1000)
    model = GraphSAGE((4, 4))
    cfg = PipelineConfig(n_parts=4, node_cap=8, edge_cap=512, repl_cap=512,
                         feat_cap=64, edge_tick_cap=256, max_nodes=200)
    pipe = D3Pipeline(model, model.init(jax.random.key(0)), cfg)
    assert pipe.part.caps == {"node_cap": 8, "edge_cap": 512,
                              "repl_cap": 512}
    with pytest.raises(RuntimeError, match=r"part \d+ .*node_cap=8"):
        pipe.tick(edges[:256])
