"""Driver-contract smoke tests: single-chip entry + multi-chip SERVED phase."""

import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_entry_compiles_and_runs():
    import __graft_entry__ as g

    fn, args = g.entry()
    mask, count = jax.jit(fn)(*args)
    assert int(count) > 0
    assert mask.shape[0] == args[0].shape[0]


@pytest.mark.parametrize("n", [2, 8])
def test_dryrun_multichip_serves_and_emits_metric(n, capsys):
    """The dry run's last line records real traffic served through the
    scheduler at mesh sizes {1, n}, byte-identical across them, with the
    platform stamp — and no rate: a CPU run gives none."""
    import __graft_entry__ as g

    g.dryrun_multichip(n)
    tail = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(tail)
    assert rec["mesh_sizes"] == [1, n]
    assert rec["byte_identical"] is True
    assert rec["served_through_scheduler"] is True
    assert rec["platform"]["platform"] == "cpu"
    assert not {"value", "rows_per_sec", "metric", "detail"} & set(rec)
