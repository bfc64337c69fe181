"""Cuts a cell to a size the CPU runs in seconds, for the tests here.
Widths shrink too: these runs check control flow and the comparison,
never speed."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def shrink(config: dict, traffic: dict):
    c, t = copy.deepcopy(config), copy.deepcopy(traffic)
    c.update(num_nodes=2000, in_dim=16, hidden_dim=8, out_dim=8, T=4,
             tick_edges=64)
    c["pipeline"].update(node_cap=512, edge_cap=512, repl_cap=512,
                         feat_cap=128, edge_tick_cap=64, query_cap=32,
                         query_tick_cap=16, max_nodes=2000)
    t["stream"]["n_edges"] = 2048
    return c, t


def run(cell: str, seed: int = 12345, seconds: float = 0.5, trace: int = 0,
        capsys=None, **kw):
    """One harness run of `cell` on the CPU at tiny sizes; returns the
    parsed result line."""
    import json
    import harness

    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      require_tpu=False, shrink=kw.pop("shrink", shrink),
                      **kw)
    assert rc == 0, rc
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)
