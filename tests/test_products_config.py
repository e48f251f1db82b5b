"""The `gcn-products` recipe at toy widths: `build_gcn([F, H, H, C])` (three
layers, ROC's projected residual) through `make_trainer` on four virtual
devices with the halo exchange and on one, on the `matmul` and the `xla`
backend, against the plain reference (`benchmark/references/gcn.py`) on
seeded weights: evaluation logits, the loss and every weight gradient, so
that 4 parts = 1 part = reference.

The gradients are the train step's own: with Adam's moments at zero and no
weight decay, one step leaves ``m = (1 - beta1) * g`` in the optimizer
state, so ``m / (1 - beta1)`` is the gradient the step applied: after the
all-reduce on four parts, to one float32 rounding.

Tolerances (relative Frobenius error; float32 everywhere on the CPU, so
what differs is the order of the sums): logits 1e-5, the loss 1e-5
relative, gradients 1e-4, the bounds `tests/benchmark/
test_benchmark_reference.py` holds the single-device program to.  A bf16
accumulate in `_one_hot_dots` reads over 1e-3 on the same graph
(`test_a_bf16_accumulate_fails`): a hundred times the logits bound, ten
times the gradients'.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, graphgen
from benchmark import manifest as mf
from benchmark import run as bench_run
from benchmark.references import gcn as ref
from roc_tpu.models import build_model
from roc_tpu.ops import aggregate as agg
from roc_tpu.train.config import Config
from roc_tpu.train.driver import make_trainer

LAYERS = [12, 16, 16, 7]            # F, H, H, C: the residual recipe
LOGITS_TOL, LOSS_TOL, GRAD_TOL = 1e-5, 1e-5, 1e-4
CASES = [(parts, backend) for parts in (4, 1)
         for backend in ("matmul", "xla")]


@pytest.fixture(scope="module")
def dataset():
    recipe = graphgen.load_recipe(os.path.join(
        mf.ROOT, "benchmark", "rehearsal", "traffic", "tiny-local-p4.json"))
    return graphgen.generate(recipe, LAYERS[0], LAYERS[-1], 5)


def _trainer(ds, parts, backend, precision="exact"):
    if len(jax.devices()) < parts:
        pytest.skip(f"needs {parts} (virtual) devices")
    cfg = Config(layers=LAYERS, model="gcn", num_parts=parts, seed=11,
                 dropout_rate=0.0, weight_decay=0.0, learning_rate=0.01,
                 aggregate_backend=backend, aggregate_precision=precision,
                 eval_every=10**9, num_epochs=1)
    return make_trainer(cfg, ds, build_model("gcn", LAYERS, 0.0))


@pytest.mark.parametrize("parts,backend", CASES)
def test_the_configuration_is_sharded_as_the_cell_shards_it(dataset, parts,
                                                            backend):
    tr = _trainer(dataset, parts, backend)
    names = sorted(jax.device_get(tr.params))
    # a main and a projection weight a layer, the output layer included
    assert names == [f"linear_{i}" for i in range(6)]
    assert tr.gdata.backend == backend
    if parts == 1:
        assert type(tr).__name__ == "Trainer"
        return
    assert type(tr).__name__ == "SpmdTrainer"
    assert checks.one_part_per_device(tr, parts)
    assert tr._exchange_mode == "halo" and tr.halo.K > 0
    assert (tr.gdata.plans is not None) == (backend == "matmul")


@pytest.mark.parametrize("parts,backend", CASES)
def test_logits_loss_and_gradients_match_the_reference(dataset, parts,
                                                       backend):
    tr = _trainer(dataset, parts, backend)
    params = jax.device_get(tr.params)
    want = ref.reference_logits(params, dataset, LAYERS, edge_block=4096)
    got = bench_run.program_logits(tr, params)
    assert got.shape == want.shape == (dataset.graph.num_nodes, LAYERS[-1])
    assert checks.rel_fro(got, want) < LOGITS_TOL
    rloss, rgrads = jax.device_get(
        ref.loss_and_grads(params, dataset, LAYERS, edge_block=4096))
    loss = float(np.asarray(tr.run_epoch()))     # the train step itself
    assert loss == pytest.approx(float(rloss), rel=LOSS_TOL)
    m = jax.device_get(tr.opt_state.m)
    assert set(m) == set(rgrads) == set(params)
    for name in sorted(m):
        grad = np.asarray(m[name]) / (1.0 - tr.optimizer.beta1)
        assert np.linalg.norm(rgrads[name]) > 0, name
        assert checks.rel_fro(grad, rgrads[name]) < GRAD_TOL, name


def test_four_parts_are_one_part(dataset):
    """Directly, not through the reference: the same seed gives the same
    initial weights, and both trainers then give the same logits and apply
    the same gradients."""
    four = _trainer(dataset, 4, "matmul")
    one = _trainer(dataset, 1, "matmul")
    params = jax.device_get(one.params)
    for name, w in jax.device_get(four.params).items():
        np.testing.assert_array_equal(w, params[name])
    a = bench_run.program_logits(four, params)
    b = bench_run.program_logits(one, params)
    assert checks.rel_fro(a, b) < LOGITS_TOL
    four.run_epoch()
    one.run_epoch()
    m4, m1 = (jax.device_get(t.opt_state.m) for t in (four, one))
    for name in m1:
        assert checks.rel_fro(m4[name], m1[name]) < GRAD_TOL, name


def test_a_bf16_accumulate_fails(dataset, monkeypatch):
    """The least a bf16 accumulate does to the matmul backend: each scan
    step's summed windows rounded to bf16 before they are added to the
    float32 carry.  Logits and gradients then read far outside the
    bounds above, on four parts."""
    whole = agg._one_hot_dots

    def rounded(g, ed, ob, cb, precision, combine_precision=None):
        return whole(g, ed, ob, cb, precision, combine_precision).astype(
            jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(agg, "_one_hot_dots", rounded)
    jax.clear_caches()
    try:
        tr = _trainer(dataset, 4, "matmul")
        params = jax.device_get(tr.params)
        got = bench_run.program_logits(tr, params)
        tr.run_epoch()
        m = jax.device_get(tr.opt_state.m)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    want = ref.reference_logits(params, dataset, LAYERS, edge_block=4096)
    _, rgrads = jax.device_get(
        ref.loss_and_grads(params, dataset, LAYERS, edge_block=4096))
    assert checks.rel_fro(got, want) > 100 * LOGITS_TOL
    worst = max(checks.rel_fro(np.asarray(m[k]) / (1.0 - tr.optimizer.beta1),
                               rgrads[k]) for k in m)
    assert worst > 5 * GRAD_TOL         # reads about 1e-3
