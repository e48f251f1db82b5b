"""tools/grad_check_sharded.py on virtual CPU devices: the cell's own train
step (four parts, halo exchange, gradient all-reduce) against the plain
reference, through the tool's command line, on a tiny cut of the
gcn-products cell and on the rehearsal's four-part cell."""

import json

import pytest

from benchmark import checks
from tools import grad_check_sharded

CASES = [
    ["--workload", "gcn-products.p4", "--nodes", "3000", "--seed", "3"],
    ["--workload", "tiny-gcn3.p4", "--seed", "2147484001",
     "--manifest", "benchmark/rehearsal/manifest.json"],
]


@pytest.mark.parametrize("argv", CASES, ids=["products-cut", "rehearsal"])
def test_the_sharded_steps_gradients_are_the_references(argv, capsys):
    rc = grad_check_sharded.main(argv + ["--rehearse-cpu"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert (out["parts"], out["exchange"]) == (4, "halo")
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 4
    # float32 everywhere on the CPU: only the order of the sums differs
    assert out["loss_rel"] < 1e-5
    assert len(out["grad_rel_fro"]) == 6        # W and P of three layers
    assert max(out["grad_rel_fro"].values()) < 1e-4 < checks.GRAD_REL_FRO_TOL
    if "--nodes" in argv:
        assert out["nodes"] == 3000


def test_without_a_tpu_nothing_runs(capsys):
    assert grad_check_sharded.main(
        ["--workload", "gcn-products.p4", "--nodes", "3000"]) == 2
    assert "no TPU" in capsys.readouterr().err
