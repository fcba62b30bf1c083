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
    """The dry run's tail is now the measured ``multichip_rows_per_sec``
    metric from real traffic served through the scheduler at mesh sizes
    {1, n} — not the old ``dryrun ok: ...`` line."""
    import __graft_entry__ as g

    g.dryrun_multichip(n)
    tail = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(tail)
    assert rec["metric"] == "multichip_rows_per_sec"
    assert rec["value"] > 0
    assert rec["platform"]["platform"] == "cpu"
    assert rec["detail"]["mesh_sizes"] == ([1, n] if n > 1 else [1])
    assert rec["detail"]["byte_identical"] is True
    assert rec["detail"]["served_through_scheduler"] is True
    assert str(n) in rec["detail"]["rows_per_sec"]
