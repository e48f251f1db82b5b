"""The plan backends' layers against a reference that is not the program.

Every layer the models build from an aggregation (GCN's normed sum,
GraphSAGE's mean, GIN's sum), alone and chained, forward and backward,
over the two-pass binned kernels at the module-default geometry (Pallas in
interpret mode on the CPU) and over the one-hot matmul backend.  The
reference is written here: float32 NumPy (`np.add.at`) for values, and
`jax.ops.segment_sum` with dots at `highest` where its autodiff supplies
the gradients.

Two contracts.  `exact`: on INTEGER data, over a graph whose in-degrees
are powers of four (so that D^-1/2 and the mean's 1/d are powers of two),
every sum is exact in float32 whatever its order, and the program must
equal the reference to the bit.  `fast`: one bf16 rounding of what is
aggregated, float32 sums after it: on continuous data each element is
within 2^-8 of what the same pipeline makes of the absolute values (half
a bf16 ulp a term; the matmul backend's default-precision dots are not
rounded by the CPU at all and sit well inside).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu import ops
from roc_tpu.analysis import retrace
from roc_tpu.analysis.retrace import RetraceGuard
from roc_tpu.graph import datasets
from roc_tpu.graph.csr import from_edges
from roc_tpu.models import build_model
from roc_tpu.models.model import Model
from roc_tpu.train.config import Config
from roc_tpu.train.driver import DenseGraphData, Trainer, make_gctx

FAMILIES = ("gcn-norm", "sage-avg", "gin-sum")
HALF_BF16_ULP = 2.0 ** -8


def _pow4_graph(seed=0, n=600):
    """In-degrees 1, 4 and 16, and one hub of 64: rsqrt and 1/d exact."""
    rng = np.random.default_rng(seed)
    deg = rng.choice([1, 4, 16], size=n, p=[0.3, 0.5, 0.2])
    deg[7] = 64
    dst = np.repeat(np.arange(n), deg)
    src = rng.integers(0, n, dst.size)
    return from_edges(n, src, dst)


def _gdata(g, backend, precision):
    """Graph data over the MODULE-DEFAULT two-pass geometry (`auto` would
    hand a graph this small to a sparse preset), or the matmul plans."""
    if backend == "binned":
        plans = ops.build_binned_plans(g.col_idx, g.dst_idx, g.num_nodes,
                                       g.num_nodes)
        assert not plans.fwd.geom.flat and plans.fwd.geom.slot == 128
    elif backend == "matmul":
        plans = ops.build_aggregate_plans(g.col_idx, g.dst_idx, g.num_nodes,
                                          g.num_nodes)
    else:
        plans = None
    return DenseGraphData(
        edge_src=jnp.asarray(g.col_idx, jnp.int32),
        edge_dst=jnp.asarray(g.dst_idx, jnp.int32),
        in_degree=jnp.asarray(g.in_degrees, jnp.float32),
        plans=plans, backend=backend, precision=precision)


def _layer_model(family, h_in, h_out, act):
    m = Model(in_dim=h_in)
    t = m.input
    if family == "gcn-norm":
        t = m.linear(t, h_out)
        t = m.indegree_norm(t)
        t = m.scatter_gather(t, "sum")
        t = m.indegree_norm(t)
        if act == "relu":
            t = m.relu(t)
    else:
        t = m.scatter_gather(t, "avg" if family == "sage-avg" else "sum")
        t = m.linear(t, h_out, activation=act)
    m.end_layer()
    m.softmax_cross_entropy(t)
    return m


# -- the reference ---------------------------------------------------------

def _agg_np(x, src, dst, n):
    out = np.zeros((n, x.shape[1]), np.float32)
    np.add.at(out, dst, x[src])
    return out


def _layer_np(family, x, w, src, dst, deg, act):
    """float32 NumPy, the operations in the model's order."""
    n = deg.size
    r = np.sqrt(deg.astype(np.float32))[:, None]
    if family == "gcn-norm":
        out = _agg_np((x @ w) / r, src, dst, n) / r
    elif family == "sage-avg":
        out = (_agg_np(x, src, dst, n) / deg[:, None].astype(np.float32)) @ w
    else:
        out = _agg_np(x, src, dst, n) @ w
    return np.maximum(out, 0) if act == "relu" else out


def _layer_jnp(family, x, w, src, dst, deg, act, rounded=False):
    """The same in jax.numpy, for its autodiff: segment_sum, `highest`.
    `rounded` is the fast path's contract: what is aggregated takes one
    bf16 rounding (straight through for the gradient), so that a relu
    gates the same elements as the program's."""
    n = deg.shape[0]
    r = jnp.sqrt(deg)[:, None]

    def agg(v):
        if rounded:
            v = v + jax.lax.stop_gradient(
                v.astype(jnp.bfloat16).astype(jnp.float32) - v)
        return jax.ops.segment_sum(v[src], dst, num_segments=n)

    def dot(a, b):
        return jnp.dot(a, b, precision="highest")

    if family == "gcn-norm":
        out = agg(dot(x, w) / r) / r
    elif family == "sage-avg":
        out = dot(agg(x) / deg[:, None], w)
    else:
        out = dot(agg(x), w)
    return jnp.maximum(out, 0) if act == "relu" else out


def _rel_fro(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _data(g, h_in, h_out, integer, seed):
    rng = np.random.default_rng(seed)
    if integer:
        x = rng.integers(-4, 5, (g.num_nodes, h_in)).astype(np.float32)
        w = rng.integers(-3, 4, (h_in, h_out)).astype(np.float32)
        c = rng.integers(-4, 5, (g.num_nodes, h_out)).astype(np.float32)
    else:
        x = rng.standard_normal((g.num_nodes, h_in), dtype=np.float32)
        w = rng.standard_normal((h_in, h_out), dtype=np.float32) * 0.3
        c = rng.standard_normal((g.num_nodes, h_out), dtype=np.float32)
    return x, w, c


# -- one layer, forward ----------------------------------------------------

@pytest.mark.parametrize("backend", ["binned", "matmul"])
@pytest.mark.parametrize("precision", ["fast", "exact"])
@pytest.mark.parametrize("act", ["none", "relu"])
@pytest.mark.parametrize("family", FAMILIES)
def test_layer_forward_matches_reference(family, act, precision, backend):
    g = _pow4_graph()
    h_in, h_out = 8, 41
    exact = precision == "exact"
    x, w, _ = _data(g, h_in, h_out, integer=exact, seed=3)
    model = _layer_model(family, h_in, h_out, act)
    gctx = make_gctx(_gdata(g, backend, precision), g.num_nodes)
    got = np.asarray(jax.jit(lambda p, v: model.apply(p, v, gctx))(
        {"linear_0": jnp.asarray(w)}, jnp.asarray(x)))
    deg = np.asarray(g.in_degrees)
    ref = _layer_np(family, x, w, g.col_idx, g.dst_idx, deg, act)
    if exact:
        np.testing.assert_array_equal(got, ref)
        return
    # one rounding of the aggregated values: relu is 1-Lipschitz, so the
    # same pipeline over absolute values bounds the error element by element
    room = _layer_np(family, np.abs(x), np.abs(w), g.col_idx, g.dst_idx,
                     deg, "none")
    assert np.all(np.abs(got - ref) <= HALF_BF16_ULP * room + 1e-5)
    assert _rel_fro(got, ref) <= 4e-3


# -- one layer, backward ---------------------------------------------------

@pytest.mark.parametrize("precision", ["fast", "exact"])
@pytest.mark.parametrize("act", ["none", "relu"])
@pytest.mark.parametrize("family", FAMILIES)
def test_layer_gradients_match_the_references_autodiff(family, act,
                                                       precision):
    """dx and dW through the binned backend (its backward is the
    transposed plan, not autodiff of the forward) against jax.grad of the
    segment_sum reference, for the cotangent `c`."""
    g = _pow4_graph(seed=1)
    h_in, h_out = 8, 24
    exact = precision == "exact"
    x, w, c = _data(g, h_in, h_out, integer=exact, seed=5)
    model = _layer_model(family, h_in, h_out, act)
    gctx = make_gctx(_gdata(g, "binned", precision), g.num_nodes)
    src, dst = jnp.asarray(g.col_idx), jnp.asarray(g.dst_idx)
    deg = jnp.asarray(g.in_degrees, jnp.float32)
    cj = jnp.asarray(c)

    def loss_program(xx, ww):
        return jnp.sum(model.apply({"linear_0": ww}, xx, gctx) * cj)

    def loss_reference(xx, ww):
        return jnp.sum(_layer_jnp(family, xx, ww, src, dst, deg, act,
                                  rounded=not exact) * cj)

    got = jax.jit(jax.grad(loss_program, argnums=(0, 1)))(
        jnp.asarray(x), jnp.asarray(w))
    ref = jax.grad(loss_reference, argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(w))
    for name, a, b in zip(("dx", "dW"), got, ref):
        if exact:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        else:
            # the cotangent takes its own rounding on the way back
            assert _rel_fro(a, b) <= 6e-3, (name, _rel_fro(a, b))


# -- chains of layers: Model.apply, binned against xla ---------------------

def _chain_model(h_in, hidden, classes, depth, last_act):
    m = Model(in_dim=h_in)
    t = m.input
    widths = [hidden] * (depth - 1) + [classes]
    for i, width in enumerate(widths):
        t = m.linear(t, width)
        t = m.indegree_norm(t)
        t = m.scatter_gather(t, "sum")
        t = m.indegree_norm(t)
        if i + 1 < depth or last_act:
            t = m.relu(t)
        m.end_layer()
    m.softmax_cross_entropy(t)
    return m


@pytest.mark.parametrize("hidden", [41, 128])
@pytest.mark.parametrize("last_act", [False, True], ids=["lin", "relu"])
@pytest.mark.parametrize("depth", [2, 3])
def test_chain_logits_and_gradients_binned_against_xla(depth, last_act,
                                                       hidden):
    """A residual-free stack of GCN layers: logits and every parameter's
    gradient over the binned kernels (`exact`: float32 to reassociation)
    against the same model over segment_sum."""
    ds = datasets.synthetic("chain", 300, 5.0, 12, 5, n_train=60, n_val=60,
                            n_test=60, seed=9)
    g = ds.graph
    model = _chain_model(ds.in_dim, hidden, ds.num_classes, depth, last_act)
    params = model.init_params(jax.random.PRNGKey(2))
    x = jnp.asarray(ds.features)
    c = jnp.asarray(np.random.default_rng(4).standard_normal(
        (g.num_nodes, ds.num_classes), dtype=np.float32))
    out = {}
    for backend in ("binned", "xla"):
        gctx = make_gctx(_gdata(g, backend, "exact"), g.num_nodes)

        def loss(p, gctx=gctx):
            logits = model.apply(p, x, gctx)
            return jnp.sum(logits * c), logits

        (_, logits), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
        out[backend] = (logits, grads)
    assert _rel_fro(out["binned"][0], out["xla"][0]) <= 2e-5
    assert sorted(out["binned"][1]) == sorted(params)
    for name in params:
        assert _rel_fro(out["binned"][1][name],
                        out["xla"][1][name]) <= 2e-5, name


# -- the cell's own shapes: a power-law graph with one hub -----------------

def _powerlaw_edges(seed=0, n=2048, e=30000, hub=3000):
    rng = np.random.default_rng(seed)
    rank = rng.permutation(n)
    p = 1.0 / (1.0 + rank) ** 0.8
    dst = rng.choice(n, size=e, p=p / p.sum())
    dst = np.concatenate([dst, np.full(hub, 11)])
    src = rng.integers(0, n, dst.size)
    order = np.argsort(dst, kind="stable")
    return src[order].astype(np.int64), dst[order].astype(np.int64), n


@pytest.fixture(scope="module")
def powerlaw_plans():
    src, dst, n = _powerlaw_edges()
    return src, dst, n, ops.build_binned_plans(src, dst, n, n)


@pytest.mark.parametrize("precision", ["fast", "exact"])
@pytest.mark.parametrize("direction", ["forward", "transposed"])
@pytest.mark.parametrize("width", [41, 128, 256])
def test_two_pass_on_a_skewed_graph_within_the_benchmarks_bound(
        powerlaw_plans, width, direction, precision):
    """The default two-pass kernels over a power-law in-degree with one hub
    row (3,000 in-edges beside rows of none), at the benchmark's widths,
    both plans of the pair: within the bound the benchmark holds `binned`
    to (6e-4, relative Frobenius, benchmark/checks.py) on non-negative
    activations; on signed values `fast` is held element by element to its
    one rounding."""
    src, dst, n, plans = powerlaw_plans
    assert plans.fwd.p1_blk.shape[0] >= 1 and not plans.fwd.geom.flat
    rng = np.random.default_rng(width)
    signed = rng.standard_normal((n, width), dtype=np.float32)
    a, b = (src, dst) if direction == "forward" else (dst, src)

    def run(v):
        v = jnp.asarray(v)
        if direction == "forward":
            return np.asarray(jax.jit(lambda t: ops.scatter_gather_binned(
                t, plans, True, precision))(v))
        _, vjp = jax.vjp(lambda t: ops.scatter_gather_binned(
            t, plans, True, precision), v)
        return np.asarray(jax.jit(vjp)(v)[0])

    act = np.abs(signed)
    assert _rel_fro(run(act), _agg_np(act, a, b, n)) <= 6e-4
    got, ref = run(signed), _agg_np(signed, a, b, n)
    room = _agg_np(np.abs(signed), a, b, n)
    tol = HALF_BF16_ULP if precision == "fast" else 2.0 ** -20
    assert np.all(np.abs(got - ref) <= tol * room + 1e-5)


# -- trainers --------------------------------------------------------------

_TRAIN = dict(num_epochs=3, learning_rate=0.01, weight_decay=5e-4,
              dropout_rate=0.0, eval_every=10 ** 9)


def _train_ds():
    return datasets.synthetic("tr", 240, 5.0, 16, 4, n_train=80, n_val=40,
                              n_test=40, seed=21)


@pytest.mark.parametrize("name", ["gcn", "sage", "gin", "gat"])
def test_trainer_plan_backends_follow_xla_without_retracing(name):
    """Three epochs through the trainer: the plan backend (binned kernels;
    for gat the plan road of attention) follows the xla run's loss curve,
    and epochs 2 and 3 re-enter the step epoch 1 traced."""
    ds = _train_ds()
    layers = [ds.in_dim, 8, ds.num_classes]
    curves = {}
    for backend in ("binned", "xla"):
        cfg = Config(layers=layers, model=name, heads=2,
                     aggregate_backend=backend, **_TRAIN)
        tr = Trainer(cfg, ds, build_model(name, layers, 0.0, "", heads=2))
        if backend == "binned":
            road = tr.gdata.gat_plans if name == "gat" else tr.gdata.plans
            assert road is not None
        losses = []
        with RetraceGuard(warmup=1) as guard:
            for _ in range(3):
                losses.append(float(tr.run_epoch()))
                retrace.epoch_boundary(len(losses))
            assert guard.counts["train_step"] == 1
        curves[backend] = losses
    assert curves["xla"][-1] < curves["xla"][0]
    np.testing.assert_allclose(curves["binned"], curves["xla"], rtol=5e-3)


def _spmd(ds, **kw):
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    layers = [ds.in_dim, 8, ds.num_classes]
    cfg = Config(layers=layers, num_parts=2, halo=True,
                 aggregate_backend="binned", **_TRAIN, **kw)
    return SpmdTrainer(cfg, ds, build_gcn(layers, 0.0))


def test_spmd_binned_zero_retraces_across_a_reshard():
    """Two CPU devices, binned plans a shard: three epochs and a same-cut
    reshard hand back the SAME jitted steps, and nothing traces again."""
    tr = _spmd(_train_ds())
    with RetraceGuard(warmup=1) as g:
        tr.train(print_fn=lambda *a, **k: None)
        assert g.counts["train_step"] >= 1
        snap = g.snapshot()
        steps = (id(tr._train_step), id(tr._eval_step))
        tr.reshard(tr.part.bounds)
        assert (id(tr._train_step), id(tr._eval_step)) == steps
        g.arm()
        tr.run_epoch()
        g.assert_no_new_traces(snap)


def test_spmd_step_cache_holds_one_entry_a_configuration():
    """The sharded step cache keys on what shapes the program: rebuilding
    for the same graph data adds nothing, the exchange's wire format (a
    static field of the data) adds exactly one."""
    import dataclasses
    tr = _spmd(_train_ds())
    assert len(tr._step_cache) == 1
    tr._build_steps(tr.gdata)
    tr.reshard(tr.part.bounds)
    assert len(tr._step_cache) == 1
    tr._build_steps(dataclasses.replace(tr.gdata, xch_dtype="bf16"))
    assert len(tr._step_cache) == 2
