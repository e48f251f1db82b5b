"""Training driver (the reference's top_level_task epoch loop, gnn.cc:99-111).

Per epoch:
  * every decay_steps epochs (not epoch 0) multiply LR by decay_rate
    (gnn.cc:100-101 — decay applied to optimizer->alpha on the host);
  * one fused train step: forward + backward + Adam (one jitted function —
    the analog of zero_gradients/forward/backward/update, except XLA fuses
    the whole epoch into one executable instead of per-op task launches);
  * every `eval_every` epochs an inference forward pass computes and prints
    the reference's metric line (gnn.cc:107-110 → softmax_kernel.cu:141-152).

`Trainer` is the single-device path; `roc_tpu.parallel.spmd.SpmdTrainer`
subclasses `BaseTrainer` for the mesh/shard_map path.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import sys
from typing import Optional

import jax
import jax.numpy as jnp

from roc_tpu import fault, obs, ops
from roc_tpu.analysis import retrace as _retrace
from roc_tpu.device import on_tpu
from roc_tpu.graph.datasets import Dataset
from roc_tpu.models.model import (PAIR_SCORES, GraphCtx, Model,
                                  attention_heads, attention_score)
from roc_tpu.ops.edge import gat_fwd_scans, gat_src_scans, short_plan_sums
from roc_tpu.ops.softmax import format_metrics
from roc_tpu.optim.adam import Adam
from roc_tpu.train.config import Config


@dataclasses.dataclass
class DenseGraphData:
    """Single-device edge arrays (a pytree, passed as jit args so the edge
    lists are runtime buffers, not compile-time constants).  ``backend`` is
    pytree *metadata* — a static string shaping the traced program."""
    edge_src: jnp.ndarray   # [E] int32
    edge_dst: jnp.ndarray   # [E] int32, sorted
    in_degree: jnp.ndarray  # [N] float32
    plans: object = None    # ops.AggregatePlans for plan-based backends
    gat_plans: object = None  # ops.edge.GatPlans for plan-backend attention
    backend: str = dataclasses.field(default="xla", metadata={"static": True})
    precision: str = dataclasses.field(default="exact",
                                       metadata={"static": True})


jax.tree_util.register_dataclass(
    DenseGraphData,
    data_fields=["edge_src", "edge_dst", "in_degree", "plans", "gat_plans"],
    meta_fields=["backend", "precision"])


def pallas_interpret() -> bool:
    """The Pallas TPU kernel runs interpreted on non-TPU backends (tests,
    CPU dev boxes)."""
    return not on_tpu()


def device_sync(x):
    """True host sync on the first leaf of ``x`` (a literal device→host
    transfer): the epoch timer stops when the value is on the host."""
    import numpy as np
    return np.asarray(jax.tree.leaves(x)[0])


# Above this many edges the "auto" backend leaves segment_sum for a
# scatter-free plan backend, on TPU only, where XLA's scatter serialises per
# index (roc_tpu/ops/aggregate.py).  CPU/GPU scatters are fine as they are.
AUTO_MATMUL_EDGES = 1 << 20
# Which plan backend: the binned kernels where their padding model holds
# (binned_viable: the benchmark's gcn-reddit cells), the one-hot matmul
# scans elsewhere (a four-chip shard of gcn-products); PERF.md section 5
# has the chip's numbers for both.  resolve_backend_why() says which test
# decided, on which statistics.
AUTO_BINNED = True


def resolve_backend_why(backend: str, num_edges: int, num_rows: int = 0,
                        table_rows: int = 0) -> tuple:
    """(backend, reason): the aggregation backend from the graph's shape and
    why, as one phrase without spaces: the flag that set it, or the test of
    the ``auto`` policy that decided with the statistics it decided on.
    The ONE place the policy lives; the sharded trainer asks it again on a
    shard's rows, table rows and fullest live edge count
    (spmd._build_graph_full) and carries the reason in its `# exchange:`
    line and ``agg_backend`` gauge.  Which geometry a binned run takes is
    decided where its plans are built (ops.build_binned_plans, from the
    actual cell statistics)."""
    if backend == "auto":
        if not on_tpu():
            return "xla", "auto:no_tpu"
        if num_edges < AUTO_MATMUL_EDGES:
            return "xla", f"auto:edges<{AUTO_MATMUL_EDGES}"
        if not AUTO_BINNED:
            return "matmul", "auto:AUTO_BINNED_off"
        if not num_rows:
            return "matmul", "auto:no_row_count"
        from roc_tpu.ops.pallas.binned import binned_viable_why
        ok, why = binned_viable_why(num_rows, table_rows, num_edges)
        return ("binned" if ok else "matmul"), "auto:" + why
    if backend == "pallas":
        # Round-1's blocked-CSR kernel cannot lower on hardware (per-row DMA
        # slices of tiled HBM refs; docs/PERF.md); "pallas" now names the
        # binned two-phase kernel pair (ops/pallas/binned.py).
        return "binned", "-aggr-backend=pallas"
    return backend, f"-aggr-backend={backend}"


def resolve_backend(backend: str, num_edges: int, num_rows: int = 0,
                    table_rows: int = 0) -> str:
    return resolve_backend_why(backend, num_edges, num_rows, table_rows)[0]


def resolve_gat_backend(backend: str, num_edges: int) -> str:
    """Attention backend: "plan" (one-hot chunk-plan softmax/aggregation,
    ops.edge.gat_attend_plan — scatter-free fwd+bwd) or "xla" (dense /
    chunked-scan gat_attend).  Same auto policy as the sum backends: plans
    pay off exactly where TPU scatter would serialize."""
    if backend == "auto":
        return "plan" if on_tpu() and num_edges >= AUTO_MATMUL_EDGES \
            else "xla"
    return "xla" if backend == "xla" else "plan"


def gat_plan_stats(plans, num_edges: int) -> dict:
    """Chunk counts and padding of a GatPlans pair (stacked plans count
    every shard): what the ``gat_plan_build`` span and the
    ``gat_plan_pad_ratio`` gauge carry."""
    d, s = plans.dst_pos, plans.src_pos
    parts = d.shape[0] if d.ndim == 3 else 1
    slots = int(d.size + s.size)
    return {"chunks_dst": int(d.size // d.shape[-1]),
            "chunks_src": int(s.size // s.shape[-1]), "slots": slots,
            "pad_ratio": slots / max(2 * parts * int(num_edges), 1)}


def model_aggrs(model: Model) -> set:
    """Aggregation kinds the built model actually uses."""
    return {op.attrs["aggr"] for op in model.ops if op.kind == "aggregate"}


# what the trainer calls a model's attention ops, by their score
# (models.model.attention_score; a builder uses one): the -model of each
# pair score, "gat" for the additive one
ATTENTION_KINDS = {"additive": "gat",
                   **{score: spec[0] for score, spec in PAIR_SCORES.items()}}


def attention_kind(model: Model) -> Optional[str]:
    """What the trainer calls the model's attention ops, all gat ops of
    the IR: "gat" (additive scores), "tconv" (dot-product scores) or
    "gatv2" (dynamic scores), None without any."""
    scores = {attention_score(op) for op in model.ops}
    return next((ATTENTION_KINDS[s] for s in ("dot", "dynamic", "additive")
                 if s in scores), None)


def model_has_attention(model: Model) -> bool:
    """Whether the model attends over in-edges (either kind): what decides
    the attention backend and whether GatPlans are built."""
    return attention_kind(model) is not None


# node tables a TRAINING step reads by row over the plans per tconv op on
# the plan road: k for the score and v for the weighted sum forward; v for
# de, k for dq, and q and du for dk and dv backward
# (ops.edge.tconv_attend_plan).  Six tables in THREE scans: q and du share
# the src-keyed scan since PR 34 (side by side, one gather by src_nid), k
# and v the backward's dst-keyed one since PR 36 (de and dq in one scan,
# one gather of [k | v] by dst_nid), and so does the forward (score and u
# in one scan, the softmax carried online).
TCONV_ROW_PASSES = 6
# scans that gather node rows per op of a pair score on the plan road, a
# training step, by score: tconv's three above; gatv2's score and u
# forward, each by dst_nid, and one scan over each plan backward
# (ops.edge.gatv2_attend_plan)
PAIR_ROW_SCANS = {"tconv": 3, "gatv2": 4}


def effective_backend_why(config: Config, dataset: Dataset, model: Model,
                          use_edge_shard: bool = False) -> tuple:
    """(backend, reason): the run's aggregation backend, model-aware: the
    plan-based backends (binned/matmul) implement sum and avg (avg =
    plan-sum / in-degree), so don't pay plan construction when the built
    model contains neither.  The reason is resolve_backend_why's, or that
    the model has no such aggregate.
    Module-level (not a trainer method) because the frozen/serving loader
    (train/frozen.py) must resolve the SAME backend as the trainer that
    wrote the checkpoint — two copies of this policy would let an
    inference process silently compile a different program than eval."""
    cfg = config
    g = dataset.graph
    no_plan_aggr = "xla", "model_has_no_sum_or_avg_aggregate"
    if use_edge_shard:
        # Edge-sharded aggregation supports xla, matmul (windowed
        # per-block one-hot plans, spmd.edge_aggregate_matmul) and,
        # where the block-window occupancy model holds, binned
        # (spmd.edge_aggregate_binned; falls back to matmul in
        # _build_graph_full otherwise).  auto resolves to matmul — the
        # binned viability bound needs the block spans, known only
        # after the edge blocks are built.
        backend, why = resolve_backend_why(cfg.aggregate_backend,
                                           g.num_edges)
        if backend in ("matmul", "binned") \
                and not ({"sum", "avg"} & model_aggrs(model)):
            if cfg.aggregate_backend != "auto":
                print(f"# aggregate_backend={cfg.aggregate_backend} "
                      f"only accelerates sum/avg aggregation under "
                      f"-edge-shard; using xla")
            return no_plan_aggr
        return backend, why
    backend, why = resolve_backend_why(cfg.aggregate_backend, g.num_edges,
                                       g.num_nodes, g.num_nodes)
    aggrs = model_aggrs(model)
    if backend in ("binned", "matmul") and not ({"sum", "avg"} & aggrs):
        if cfg.aggregate_backend != "auto" \
                and not model_has_attention(model):
            # (an attention model honors the choice through the attention
            # plan backend instead — effective_gat_backend)
            print(f"# aggregate_backend={backend} only accelerates "
                  f"sum/avg aggregation; this model uses "
                  f"{sorted(aggrs)} — using xla")
        return no_plan_aggr
    return backend, why


def effective_backend(config: Config, dataset: Dataset, model: Model,
                      use_edge_shard: bool = False) -> str:
    return effective_backend_why(config, dataset, model, use_edge_shard)[0]


def effective_gat_backend(config: Config, dataset: Dataset,
                          model: Model) -> str:
    """Attention backend for models with gat or tconv ops ("plan" |
    "xla"); both kinds ride the same GatPlans."""
    if not model_has_attention(model):
        return "xla"
    return resolve_gat_backend(config.aggregate_backend,
                               dataset.graph.num_edges)


def maybe_autotune(edge_src, edge_dst, num_rows: int, table_rows: int,
                   storage_dtype: str = "fp32", watchdog=None, log=None):
    """-autotune / ROC_AUTOTUNE: sweep this graph's kernel-config space
    (roc_tpu/tune) and persist the winners in the tuned store BEFORE the
    plan builds below, so choose_geometry / build_binned_plan pick them
    up on this very run.  Surrogate trials off-hardware, real timed
    trials on TPU.  Failure-isolated: a tuner error must never take the
    training run down with it."""
    import numpy as np
    try:
        from roc_tpu.tune import autotune_graph
        with obs.span("autotune", edges=int(np.asarray(edge_src).size)):
            return autotune_graph(
                np.asarray(edge_src), np.asarray(edge_dst), num_rows,
                table_rows, storage_dtype=storage_dtype,
                device=on_tpu(),
                watchdog=watchdog, log=log)
    except Exception as e:      # pragma: no cover - defensive
        import warnings
        warnings.warn(f"autotune failed ({e}); continuing untuned")
        return None, None


def dense_graph_data(graph, backend: str = "xla",
                     precision: str = "exact",
                     gat_backend: str = "xla",
                     storage_dtype: str = "fp32",
                     autotune: bool = False,
                     attention: str = "gat") -> DenseGraphData:
    """``attention``: the op kind the attention plans serve (what the
    ``gat_plan_build`` span says; the plans are the same for both)."""
    if autotune:
        maybe_autotune(graph.col_idx, graph.dst_idx, graph.num_nodes,
                       graph.num_nodes, storage_dtype=storage_dtype)
    backend = resolve_backend(backend, graph.num_edges, graph.num_nodes,
                              graph.num_nodes)
    plans = None
    with obs.span("plan_build", backend=backend):
        if backend == "matmul":
            plans = ops.build_aggregate_plans(
                graph.col_idx, graph.dst_idx, graph.num_nodes,
                graph.num_nodes)
        elif backend == "binned":
            plans = ops.build_binned_plans(
                graph.col_idx, graph.dst_idx, graph.num_nodes,
                graph.num_nodes, geom="auto", storage_dtype=storage_dtype)
        gat_plans = None
        if gat_backend == "plan":
            from roc_tpu.ops.edge import build_gat_plans
            with obs.span("gat_plan_build", edges=graph.num_edges,
                          serves=attention) as sp:
                gat_plans = build_gat_plans(graph.col_idx, graph.dst_idx,
                                            graph.num_nodes, graph.num_nodes)
                sp.args.update(gat_plan_stats(gat_plans, graph.num_edges))
    with obs.span("place_data", what="edges"):
        return DenseGraphData(
            edge_src=jnp.asarray(graph.col_idx, jnp.int32),
            edge_dst=jnp.asarray(graph.dst_idx, jnp.int32),
            in_degree=jnp.asarray(graph.in_degrees, jnp.float32),
            plans=plans,
            gat_plans=gat_plans,
            backend=backend,
            precision=precision,
        )


def make_gctx(g: DenseGraphData, num_nodes: int) -> GraphCtx:
    interp = pallas_interpret()

    def aggregate(x, aggr):
        # avg rides the sum fast path: avg = sum / in-degree (in_degree is
        # the live in-edge count — GraphSAGE-mean gets the plan backends).
        if g.plans is not None and aggr in ("sum", "avg"):
            if g.backend == "binned":
                out = ops.scatter_gather_binned(x, g.plans, interp,
                                                g.precision)
            else:
                out = ops.scatter_gather_matmul(
                    x, g.plans, num_nodes, x.shape[0],
                    ops.matmul_precision(g.precision))
            if aggr == "avg":
                out = ops.divide_by_degree(out, g.in_degree)
            return out
        return ops.scatter_gather(x, g.edge_src, g.edge_dst, num_nodes, aggr)

    def attend(h, a_src, a_dst, slope, drop=None):
        # single device: the source table IS the local tensor
        if g.gat_plans is not None:
            from roc_tpu.ops.edge import gat_attend_plan
            return gat_attend_plan(h, h, a_src, a_dst, g.gat_plans,
                                   (g.edge_src, g.edge_dst), slope,
                                   ops.matmul_precision(g.precision), drop)
        return ops.gat_attend(h, h, g.edge_src, g.edge_dst, num_nodes,
                              a_src, a_dst, slope, drop)

    def attend_pair(score, tables, drop=None, **attrs):
        # dot-product or dynamic scores over the same plans; one table set
        # a device; float32 at "highest" whatever g.precision
        # (tconv_attend_plan, gatv2_attend_plan)
        plan, dense = {"dot": (ops.tconv_attend_plan, ops.tconv_attend),
                       "dynamic": (ops.gatv2_attend_plan,
                                   ops.gatv2_attend)}[score]
        if g.gat_plans is not None:
            return plan(*tables, g.gat_plans, g.edge_src.shape[0], drop,
                        **attrs)
        return dense(*tables, g.edge_src, g.edge_dst, num_nodes, drop,
                     **attrs)

    return GraphCtx(aggregate=aggregate, in_degree=g.in_degree,
                    attend=attend, attend_pair=attend_pair)


@dataclasses.dataclass
class TrainStats:
    """What one ``train()`` call measured — the single source of truth for
    epoch timings (bench.py and the balance telemetry both consume this
    instead of re-deriving their own).  ``epoch_times`` excludes everything
    that happens between epochs (eval, checkpointing, balance rounds);
    ``total_s`` includes it all."""

    epoch_times: list
    total_s: float
    epochs: int
    final_loss: float
    rebalance_events: list = dataclasses.field(default_factory=list)
    # per-epoch peak HBM (bytes): device-reported where the backend exposes
    # memory_stats (TPU), the memory planner's prediction elsewhere;
    # ``peak_hbm_source`` says which ("measured" | "estimated" | "")
    peak_hbm_bytes: list = dataclasses.field(default_factory=list)
    peak_hbm_source: str = ""


# Consecutive guarded-skip steps before the escalation engages (restore
# from the last durable checkpoint).  One bad batch skips silently; K in
# a row means the run is not recovering on its own.
NONFINITE_ESCALATE_AFTER = 3


class BaseTrainer:
    """Shared epoch loop, LR decay, metrics cadence, checkpointing."""

    def __init__(self, config: Config, dataset: Dataset, model: Model):
        self.config = config
        self.dataset = dataset
        self.model = model
        self.optimizer = Adam(alpha=config.learning_rate,
                              weight_decay=config.weight_decay)
        with obs.span("init_params", what="key"):   # the first device op
            self.key = jax.random.PRNGKey(config.seed)
        self.epoch = 0
        self.dtype = jnp.bfloat16 if config.use_bf16 else jnp.float32
        # fault harness: arm -fault specs that arrived via the flag (the
        # ROC_FAULT env path armed at roc_tpu.fault import); host side of
        # the in-graph non-finite guard + its escalation ladder
        if config.fault and config.fault != fault.spec():
            fault.configure(config.fault)
        self._last_nonfinite = None
        self._nf_streak = 0
        self._nf_skips = 0
        self._stop_signal = None
        # Edge-sharded aggregation is a multi-device strategy; SpmdTrainer
        # resolves "auto" from measured partition skew during _setup.
        self._use_edge_shard = False
        self._obs_init()
        self._setup()
        self.announce()
        self.balancer = None
        if config.balance_every:
            if self._balance_supported():
                from roc_tpu.balance.manager import BalanceManager
                # Warm-start prior priced at the run's actual halo bytes:
                # the dataset's feature width and the wire itemsize (bf16
                # storage and bf16 features both exchange 2-byte rows).
                wire2 = config.bf16_storage or config.use_bf16
                # A -obs run funnels balance telemetry through the obs
                # metrics stream (one JSONL, one schema) unless the user
                # pinned a separate -balance-trace path.
                shared = self._metrics.telemetry \
                    if (self._metrics is not None
                        and not config.balance_trace) else None
                self.balancer = BalanceManager.from_config(
                    config, halo_width=self.dataset.in_dim,
                    halo_itemsize=2 if wire2 else 4, telemetry=shared)
                # stragglers the balancer probes feed the same watchdog
                self.balancer.watchdog = self.watchdog
            elif config.verbose:
                print("# -balance-every: online balancing needs the SPMD "
                      "vertex-sharded path (parts > 1, k = 1, no "
                      "-perhost/-edge-shard/ring); disabled for this run")
        if config.resume and config.checkpoint_path and \
                os.path.exists(config.checkpoint_path):
            self.restore(config.checkpoint_path)

    def _balance_supported(self) -> bool:
        """Can this trainer apply a repartition mid-run?  The SPMD trainer
        overrides this for the modes ``reshard`` handles."""
        return False

    # -- observability (roc_tpu/obs) --------------------------------------
    def _obs_init(self):
        """Arm the obs layer before _setup so plan-build spans record and
        the step builders see cfg.obs when shaping their outputs."""
        cfg = self.config
        self._metrics = None
        self._step_scopes_announced = False
        self.watchdog = None
        self._last_step_metrics = None
        if not cfg.obs:
            return
        obs.enable(True)
        jsonl = os.path.join(cfg.obs_dir, "metrics.jsonl") \
            if cfg.obs_dir else ""
        if jsonl:
            try:
                os.makedirs(cfg.obs_dir, exist_ok=True)
            except OSError:
                jsonl = ""  # keep the in-memory registry; skip the file
        self._metrics = obs.MetricsRegistry(jsonl_path=jsonl)
        # retry/injection events from the fault harness land in the same
        # JSONL stream as the metrics records (detached in _obs_finish)
        fault.attach(self._metrics.emit)
        # Calibration ledger -> this run's stream: every cost-model
        # prediction/measurement pair (plan steps, step time, peak HBM,
        # wire bytes, ...) lands next to the epoch records it describes.
        # Detached again in _obs_finish.
        obs.get_ledger().attach(self._metrics.emit)
        g = self.dataset.graph
        # Static per-epoch roofline inputs (obs/roofline.py — the same
        # accounting bench.py reports) for the mfu / roofline_frac fields
        # stamped on every metrics record.  Claimed on a TPU only, against
        # that device kind's published peaks (an unknown kind raises here,
        # before the first epoch, not after it).
        prec = "fast" if (cfg.use_bf16
                          or getattr(cfg, "bf16_storage", False)) else "exact"
        self._roofline_fb = obs.roofline.model_flops_bytes(
            self.model, g.num_nodes, g.num_edges, precision=prec)
        self._roofline_kind = None
        if on_tpu():
            self._roofline_kind = jax.devices()[0].device_kind
            obs.roofline.peaks_for(self._roofline_kind)
        # EWMA seeded from the committed kernel-budget prediction when the
        # graph shape is pinned there (binned runs); None -> measured warmup
        self.watchdog = obs.PerfWatchdog(
            seed_s=obs.seed_for_graph(g.num_nodes, g.num_edges))

    def attention_info(self) -> Optional[dict]:
        """What this trainer resolved for its attention ops (None: the
        model has none), keyed by what the ``# attention:`` line, the
        `attention` record and the gauges call it less the op kind's
        prefix (``gat_`` / ``tconv_`` / ``gatv2_``, :func:`attention_kind`):

        ``backend``: "plan" (ops.edge.gat_attend_plan / tconv_attend_plan /
        gatv2_attend_plan or the sharded kin over GatPlans) or "xla" (the
        dense / chunked / ring scans); ``plan_pad_ratio``: the GatPlans'
        slots over edges;
        ``score_bytes`` (gat): the per-edge residuals a train step keeps
        between forward and backward on the plan path (e float32 + the
        score's sign, [K, E] each, per op; 0 where autodiff keeps what it
        likes); ``dst_reads`` (gat): how node tables are read by
        ``edge_dst`` ("plan": the aligned dst plan's segment broadcast;
        "gather": by index, the xla scans); ``fwd_scans`` (gat): the scans
        over the plans a training step's gat forwards make
        (ops.edge.gat_fwd_scans: 2 an op, ``su`` and the max's broadcast;
        5 or 6 on the edge-sharded road; 0 on the xla scans).  A tconv
        model instead says ``score`` ("dot"), ``score_bytes`` (ONE [K, E]
        float32 array of its widest op: what each per-edge array live in a
        layer's backward costs), ``residual_bytes`` (the [K, E] bytes kept
        for the backward, e of every op), ``row_passes`` (tables a training
        step reads by row over the plans, 6 an op: k, v forward; v, k, q, du
        backward) and ``row_scans`` (the scans that gather them by an index
        list, 3 an op: [k | v] side by side for the score and u, and again
        for de and dq, over the dst-keyed plan, and [q | du] for dk and dv
        over the src-keyed one; 0 on the xla road).  A gatv2 model says the
        same but ``row_passes``: ``score`` ("dynamic"), ``score_bytes``,
        ``residual_bytes`` (its e, by memory.estimator's count: no sign is
        kept) and ``row_scans`` (4 an op: score and u over xl rows; xl
        again for de, ds, dxr and da over the dst-keyed plan, [xr | du] for
        dxl over the src-keyed one).  Every kind ends
        with ``src_scans``: the scans over the src-keyed plan a training
        step makes, all in the backward: 1 an op (tconv: dk and dv
        together; gatv2: both terms of dxl; gat: dast riding dtable's
        where ops.edge.gat_src_scans lets it), 2 an op on the edge-sharded
        road (parallel/spmd.py ``_egat_bwd``), 0 on the xla scans; then
        ``short_scans``: the row-gathering sums a training step makes
        (``u`` forward, but tconv's and gat's, which their score's scan
        carries, gat's on the edge-sharded road excepted; the src side's
        rows backward: K F wide, tconv's and gatv2's src side 2 K F) at a
        step shorter than ops.edge's cap, by
        ops.edge.plan_sum_step, the rule they are stepped by; 0 on the xla
        scans."""
        kind = attention_kind(self.model)
        if kind is None:
            return None
        gd = getattr(self, "gdata", None)   # the streamed trainer has none
        plans = getattr(gd, "gat_plans", None)
        plans = getattr(plans, "plans", plans)      # EdgeGatPlans wraps one
        on_plan = plans is not None
        edges = int(gd.edge_src.shape[-1]) if on_plan else 0    # per shard
        heads = [attention_heads(op) for op in self.model.ops
                 if op.kind == "gat"]
        info = {"backend": "plan" if on_plan else "xla",
                "plan_pad_ratio": gat_plan_stats(plans, edges)["pad_ratio"]
                if on_plan else 0.0}
        sharded = plans is not getattr(gd, "gat_plans", None)
        if kind == "gat":
            info["score_bytes"] = sum(heads) * edges * (4 + 1)
            info["dst_reads"] = "plan" if on_plan else "gather"
            info["fwd_scans"] = len(heads) * gat_fwd_scans(
                edges, sharded) if on_plan else 0
        else:
            from roc_tpu.memory.estimator import gat_edge_residual_bytes
            info.update(score="dot" if kind == "tconv" else "dynamic",
                        score_bytes=max(heads) * edges * 4,
                        residual_bytes=sum(
                            gat_edge_residual_bytes(op, edges)
                            for op in self.model.ops))
            if kind == "tconv":
                info["row_passes"] = TCONV_ROW_PASSES * len(heads)
            info["row_scans"] = PAIR_ROW_SCANS[kind] * len(heads) \
                if on_plan else 0

        def src_scans(k):       # of one op of k heads, a training step
            if not on_plan:
                return 0
            if sharded:         # parallel/spmd.py _egat_bwd keeps two calls
                return 2
            return gat_src_scans(k) if kind == "gat" else 1

        info["src_scans"] = sum(map(src_scans, heads))

        def row_widths(op):     # u's rows, then the src side's
            kf = attention_heads(op) * op.attrs["head_dim"]
            if kind == "tconv":     # u rides the score's scan
                return (2 * kf,)
            if kind == "gat":       # so does gat's, but edge-sharded
                return (kf, kf) if sharded else (kf,)
            return kf, 2 * kf

        info["short_scans"] = short_plan_sums(
            [w for op in self.model.ops if op.kind == "gat"
             for w in row_widths(op)]) if on_plan else 0
        return info

    def announce(self):
        """Everything this trainer has to say of itself, to stderr and,
        where it holds a metrics registry, as records and gauges: the
        attention's facts, the memory plan's verdicts, the step's own count
        of its device scopes (and the sharded trainer's exchange before
        them).  At start-up; and the
        public name for a caller that lends a registry after a run and
        wants the gauges made again, as the benchmark's traced run does."""
        self._announce_attention_info()
        self._announce_mem_plan()
        self._announce_step_scopes()

    # benchmark/run.py's `program_gauges` (no file of the benchmark is a
    # tracing PR's to edit) still asks for the announcements by the two
    # private names they had when there were two; this is the one a
    # one-chip trainer answers to.  It goes when the harness calls
    # `announce()` (PERF.md section 7 item 6).
    _announce_attention = announce

    def _announce_attention_info(self):
        """The trainer's own start-up line for an attention model, and the
        same facts as `attention` record + gauges under -obs: numbers as
        unlabelled gauges, texts as labelled ones, every name prefixed
        with the op kind."""
        info = self.attention_info()
        if info is None:
            return
        kind = attention_kind(self.model)
        backend = info.pop("backend")
        record = {"backend": backend,
                  **{f"{kind}_{k}": v for k, v in info.items()}}
        from roc_tpu.obs.report import attention_line
        print(attention_line(record), file=sys.stderr, flush=True)
        if self._metrics is not None:
            self._metrics.emit("attention", **record)
            self._metrics.set_gauge(f"{kind}_backend", 1.0, backend=backend)
            for k, v in info.items():
                if isinstance(v, str):
                    self._metrics.set_gauge(f"{kind}_{k}", 1.0, **{k: v})
                else:
                    self._metrics.set_gauge(f"{kind}_{k}", v)

    def _announce_mem_plan(self):
        """What the memory plan decided (roc_tpu/memory), as unlabelled
        gauges, under every mode: ``mem_plan_remat_layers`` /
        ``mem_plan_kept_layers`` (closed layers by verdict; OFFLOAD counts
        as remat where it executes as one), ``mem_plan_saved_bytes`` (what
        the plan holds from forward to backward by the estimator's count:
        the kept layers' tagged outputs and every layer's pinned ones; an
        all-KEEP step runs unwrapped and is priced at every op's output)
        and ``mem_plan_predicted_peak_bytes`` (the planner's forecast,
        which the benchmark's `peak_hbm_gib` stands beside)."""
        plan = getattr(self, "mem_plan", None)
        if self._metrics is None or plan is None:
            return
        from roc_tpu import memory
        # the forecast itself was ledgered where it was made
        # (_resolve_mem_plan); this is the plan's field as a gauge
        for name, value in (
                ("remat_layers", plan.num_remat()),
                ("kept_layers", len(plan.decisions) - plan.num_remat()),
                ("saved_bytes", memory.saved_bytes(self.mem_estimate,
                                                   plan.decisions)),
                ("predicted_peak_bytes", plan.predicted_peak_bytes)):
            self._metrics.set_gauge(f"mem_plan_{name}", value)

    def _announce_step_scopes(self):
        """What the train step's own lowering says of its device scopes,
        as unlabelled gauges: ``step_whiles`` (its `stablehlo.while`s, on
        this platform's lowering: the CPU's random-bit generators are
        loops, the chip's are not) and ``step_unscoped_share`` (per cent
        of its heavy ops that sit under no `roc.` scope,
        obs.scopes.lowered_counts: 0.0 while every kind of device work is
        issued under its name).  Only for a trainer that holds a metrics
        registry and whose steps have run: `-obs`, once, when ``train()``
        first ends (before ``metrics.prom`` is written; at start-up it
        would trace the step ahead of its first call); the benchmark's
        traced run, when it lends a registry after its window.  It lowers
        the train step again (a lookup once it ran), compiles nothing, and
        a run without a registry never comes here."""
        if (self._metrics is None or self.epoch == 0
                or not hasattr(self, "_train_step")):
            return
        from roc_tpu.analysis.hlo_audit import lower_train_step
        from roc_tpu.obs import scopes
        with obs.span("step_scopes"):       # what the lowering costs
            counts = scopes.lowered_counts(lower_train_step(self))
        self._metrics.set_gauge("step_whiles", counts["whiles"])
        self._metrics.set_gauge(
            "step_unscoped_share",
            100.0 * counts["heavy_unscoped"] / max(counts["heavy"], 1))
        self._step_scopes_announced = True

    def device_scopes(self) -> dict:
        """{"train": map, "eval": map}: for each of the two steps the
        epoch loop runs, {instruction name: (op, pass, part)} of its
        compiled executable (obs.scopes.describe_module).  The programs
        are lowered anew and compiled WITHOUT JAX's persistent cache,
        whose executables carry the names of whoever compiled them first
        (obs/scopes.py); the instruction names are those of the
        executables the steps run.  Minutes on a chip at a cell's size, so
        nothing on the training path calls it: `-profile`, `python -m
        roc_tpu.obs report -profile`, tools/device_by_scope.py."""
        return {name: program["scopes"]
                for name, program in self._device_programs().items()}

    def _device_programs(self) -> dict:
        """Per step: its module's name in a trace, the instruction map and
        the count of fusions that mix op scopes."""
        from roc_tpu.analysis.hlo_audit import lower_steps
        from roc_tpu.obs import scopes
        return {name: scopes.describe_module(scopes.compile_uncached(lowered))
                for name, lowered in lower_steps(self).items()}

    def _write_device_scopes(self, profile_dir: str, print_fn) -> None:
        """``<profile_dir>/roc_scopes.json``: what `python -m roc_tpu.obs
        report -profile` joins the trace with (the programs' maps, the
        model's op list, the versions the instruction names belong to).
        Observability never kills a run: whatever the compile or the file
        system raises is one printed line."""
        import json
        path = os.path.join(profile_dir, "roc_scopes.json")
        try:
            with obs.span("device_scopes") as sp:
                programs = self._device_programs()
            record = {
                "jax": jax.__version__,
                # the runtime's own words, on one line: libtpu's build on
                # a TPU
                "platform_version": " ".join(
                    jax.devices()[0].client.platform_version.split()),
                "ops": [{"index": i, "kind": op.kind,
                         "layer": int(op.attrs.get("layer", 0))}
                        for i, op in enumerate(self.model.ops)],
                "programs": programs}
            os.makedirs(profile_dir, exist_ok=True)
            with open(path, "w", encoding="utf-8") as f:
                json.dump(record, f)
        except Exception as e:      # a backend that refuses the compile, a
            # full disk: the run and its checkpoint stand
            print_fn(f"# device scopes not written to {path}: "
                     f"{type(e).__name__}: {e}")
            return
        print_fn(f"# device scopes written to {path} ({sp.dur_s:.1f} s of "
                 f"lowering and compiling {len(programs)} programs)")

    def _obs_epoch(self, epoch: int, wall_s: float, loss, print_fn):
        """Per-epoch drain: fetch the in-graph metrics pytree (ONE
        device_get, after the timed window so it never pollutes
        epoch_times), emit the unified record, feed the watchdog."""
        if self._metrics is None:
            return
        rec = {"epoch": int(epoch), "wall_s": round(float(wall_s), 6),
               "loss": float(jax.device_get(loss))}
        if self._last_step_metrics is not None:
            with obs.span("metrics_fetch"):
                vals = jax.device_get(self._last_step_metrics)
            rec["grad_norm"] = float(vals["grad_norm"])
            rec["param_norm"] = float(vals["param_norm"])
            rec["wire_bytes"] = int(vals["wire_bytes"])
            rec["edges_per_shard"] = [int(e) for e in vals["edges"]]
        extra = self._obs_epoch_extra(epoch)
        if extra:
            rec.update(extra)
        kind = self._roofline_kind
        if kind is not None:
            # per-epoch roofline standings, same accounting bench.py
            # stamps into artifacts (only claimed on a TPU)
            flops, nbytes = self._roofline_fb
            n_dev = jax.device_count()
            m = obs.roofline.mfu(flops, wall_s, n_dev, kind)
            if m is not None:
                rec["mfu"] = round(m, 4)
                rec["roofline_frac"] = round(obs.roofline.roofline_frac(
                    flops, nbytes, wall_s, n_dev, kind), 4)
        self._metrics.emit("metrics", **rec)
        led = obs.get_ledger()
        key = getattr(self, "_calib_key", None)
        if led.attached and key is not None:
            # measurement halves of _resolve_mem_plan's predictions (+ the
            # SPMD wire-bytes analytic, keyed at step-build time)
            led.measure("step_time", key, wall_s, "s", epoch=int(epoch))
            wk = getattr(self, "_wire_key", None)
            if wk is not None and rec.get("wire_bytes"):
                led.measure("wire_bytes", wk, rec["wire_bytes"], "bytes",
                            epoch=int(epoch))
            hbm, src = self._peak_hbm()
            if src == "measured":
                led.measure("peak_memory", key, hbm, "bytes",
                            epoch=int(epoch))
        if self.watchdog is not None:
            alert = self.watchdog.observe_epoch(epoch, wall_s)
            if alert is not None:
                self._metrics.emit("watchdog", **alert)
                if self.config.verbose:
                    print_fn(f"# watchdog: epoch {epoch} took "
                             f"{alert['ratio']:.2f}x the EWMA "
                             f"({alert['wall_s'] * 1e3:.1f} ms vs "
                             f"{alert['ewma_s'] * 1e3:.1f} ms)")
            if extra and "stream_stall_frac" in extra:
                alert = self.watchdog.observe_stream(
                    epoch, extra["stream_stall_frac"])
                if alert is not None:
                    self._metrics.emit("watchdog", **alert)
                    if self.config.verbose:
                        print_fn(
                            f"# watchdog: epoch {epoch} stream stall "
                            f"fraction {alert['stall_frac']:.3f} is "
                            f"{alert['ratio']:.2f}x its EWMA "
                            f"({alert['ewma']:.3f})")
            if extra and "stream_spill_stall_frac" in extra:
                alert = self.watchdog.observe_spill(
                    epoch, extra["stream_spill_stall_frac"])
                if alert is not None:
                    self._metrics.emit("watchdog", **alert)
                    if self.config.verbose:
                        print_fn(
                            f"# watchdog: epoch {epoch} spill stall "
                            f"fraction {alert['stall_frac']:.3f} is "
                            f"{alert['ratio']:.2f}x its EWMA "
                            f"({alert['ewma']:.3f})")
            # Calibration drift: the pairs joined this epoch feed the
            # per-model ratio EWMAs.  Off the TPU backends only the
            # structurally-exact models are judged — the time models'
            # constants were fit on hardware, so a CPU run's step_time
            # ratio is meaningless, not drifted.
            for mname, ratio in led.drain_ratios():
                if kind is None and \
                        mname not in ("plan_steps", "staging_rows",
                                      "wire_bytes"):
                    continue
                alert = self.watchdog.observe_calibration(mname, ratio,
                                                          epoch)
                if alert is not None:
                    self._metrics.emit("watchdog", **alert)
                    if self.config.verbose:
                        print_fn(
                            f"# watchdog: cost model {mname} ratio EWMA "
                            f"{alert['ewma_ratio']:.3g} left the band "
                            f"[{alert['band_lo']:.2g}, "
                            f"{alert['band_hi']:.2g}]")

    def _obs_epoch_extra(self, epoch):
        """Executor-specific per-epoch obs fields (the stream executor
        reports stall/overlap here); merged into the unified record."""
        del epoch
        return None

    def _obs_finish(self, stats: "TrainStats", print_fn):
        """End-of-train summary record + artifact export (trace.json /
        metrics.prom under -obs-dir)."""
        if self._metrics is None:
            return
        cfg = self.config
        # the ledger outlives the run (process singleton); stop routing
        # its records into this run's stream
        obs.get_ledger().detach()
        fault.detach()
        if not self._step_scopes_announced:     # once a trainer
            self._announce_step_scopes()
        verdict = self.watchdog.verdict() if self.watchdog else "off"
        self._metrics.emit(
            "train", epochs=stats.epochs, total_s=round(stats.total_s, 6),
            final_loss=stats.final_loss, watchdog_verdict=verdict,
            watchdog_alerts=len(self.watchdog.alerts)
            if self.watchdog else 0)
        if cfg.obs_dir:
            trace_path = os.path.join(cfg.obs_dir, "trace.json")
            ok = obs.get_tracer().write_chrome_trace(trace_path)
            self._metrics.write_prometheus(
                os.path.join(cfg.obs_dir, "metrics.prom"))
            if cfg.verbose and ok:
                print_fn(f"# obs: trace -> {trace_path} "
                         f"({len(obs.get_tracer().span_types())} span "
                         f"types); watchdog verdict: {verdict}")

    def _resolve_mem_plan(self):
        """Choose this run's activation-memory plan (roc_tpu/memory) from
        -mem-plan / -mem-budget.  Called once per _setup, before the steps
        are traced; reshards keep the plan, so the step cache (keyed on
        ``mem_plan.key()``) still hits."""
        from roc_tpu import memory
        cfg = self.config
        self.mem_estimate = memory.estimate_for_trainer(self)
        budget = cfg.mem_budget_bytes()
        if cfg.mem_plan == "auto" and budget == 0:
            budget = memory.device_budget_bytes()
        self.mem_plan = memory.plan_memory(
            self.mem_estimate, mode=cfg.mem_plan, budget_bytes=budget,
            offload_executed=getattr(cfg, "stream", False),
            offload_spills=bool(getattr(cfg, "stream_spill", "")))
        # Ledger predictions made once, before the first epoch: the
        # estimator's all-KEEP step time and the memory plan's peak —
        # paired per epoch in _obs_epoch (wall clock / device-reported
        # peak) under one content key for the run's shard shape.
        led = obs.get_ledger()
        if led.attached:
            from roc_tpu.obs.ledger import content_key
            self._calib_key = content_key(rows=self.mem_estimate.rows,
                                          edges=self.mem_estimate.edges)
            led.predict("step_time", self._calib_key,
                        self.mem_estimate.base_step_s, "s")
            led.predict("peak_memory", self._calib_key,
                        self.mem_plan.predicted_peak_bytes, "bytes")
        if cfg.verbose and (cfg.mem_plan != "keep" or budget):
            print(f"# {self.mem_plan.summary()}")

    def _loss_fn(self):
        """``model.loss`` with the memory plan's checkpoint policy applied
        (the model's own loss when the plan keeps everything)."""
        from roc_tpu.memory import policy as mem_policy
        return mem_policy.loss_fn(self.model, getattr(self, "mem_plan", None),
                                  offload_to_host=getattr(
                                      self.config, "stream", False))

    def _peak_hbm(self):
        """(bytes, source) for this epoch's peak HBM: device-reported where
        the backend exposes memory_stats, the plan's prediction otherwise."""
        from roc_tpu import memory
        measured = memory.measured_peak_bytes()
        if measured is not None:
            return measured, "measured"
        plan = getattr(self, "mem_plan", None)
        if plan is not None:
            return plan.predicted_peak_bytes, "estimated"
        return 0, ""

    # subclasses: place data (x/labels/mask/gdata), init params/opt_state,
    # and build the jitted self._train_step / self._eval_step
    def _setup(self):
        raise NotImplementedError

    def _effective_backend(self) -> str:
        """Also remembers why (``self._backend_why``), for the sharded
        trainer's `# exchange:` line."""
        backend, self._backend_why = effective_backend_why(
            self.config, self.dataset, self.model,
            use_edge_shard=self._use_edge_shard)
        return backend

    def _gat_backend(self) -> str:
        return effective_gat_backend(self.config, self.dataset, self.model)

    def _model_aggrs(self) -> set:
        """Aggregation kinds the built model actually uses (backend and
        edge-shard selection both key off this)."""
        return model_aggrs(self.model)

    def _aggregate_widths(self) -> list:
        """Feature width at each aggregate/gat op, in op order — the widths
        a forward pass exchanges at (obs wire-byte accounting).  The op IR
        stores tensor ids, not dims, so track the last linear's out_dim
        (builders always aggregate a projected tensor; the input width
        covers a hypothetical pre-projection aggregate)."""
        widths, width = [], self.dataset.in_dim
        for op in self.model.ops:
            if op.kind == "linear":
                width = op.attrs["out_dim"]
            elif op.kind in ("aggregate", "gat"):
                widths.append(width)
        return widths

    def _model_has_gat(self) -> bool:
        return any(op.kind == "gat" for op in self.model.ops)

    def _run_step(self, step_key, alpha):
        out = self._train_step(
            self.params, self.opt_state, self.x, self.labels, self.mask,
            self.gdata, step_key, alpha, fault.nan_scale())
        if self.config.obs:
            # the in-graph metrics pytree rides the step outputs; stash it
            # device-side — _obs_epoch fetches once after the timed window
            (self.params, self.opt_state, loss, self._last_nonfinite,
             self._last_step_metrics) = out
        else:
            (self.params, self.opt_state, loss,
             self._last_nonfinite) = out
        return loss

    # -- non-finite step guard, host side (roc_tpu/fault/guard.py) --------
    def _check_nonfinite(self, epoch: int, print_fn) -> None:
        """Read the step's in-graph skip flag (the epoch sync already
        landed, so this device_get is a ready-scalar fetch, not a stall),
        track the consecutive-skip streak, and walk the escalation ladder
        when the guard alone stops recovering."""
        if self._last_nonfinite is None:
            return
        if not bool(jax.device_get(self._last_nonfinite)):
            self._nf_streak = 0
            return
        self._nf_streak += 1
        self._nf_skips += 1
        if self.watchdog is not None:
            alert = self.watchdog.observe_nonfinite(epoch, self._nf_streak)
            if alert is not None and self._metrics is not None:
                self._metrics.emit("watchdog", **alert)
        if self.config.verbose:
            print_fn(f"# fault: non-finite loss/grads at epoch {epoch}; "
                     f"update skipped (streak {self._nf_streak})")
        if self._nf_streak >= NONFINITE_ESCALATE_AFTER:
            self._escalate_nonfinite(epoch, print_fn)
            self._nf_streak = 0

    def _escalate_nonfinite(self, epoch: int, print_fn) -> None:
        """K consecutive skipped steps: restore params/optimizer state
        from the last durable checkpoint and keep going, or say that
        there is none."""
        cfg = self.config
        path = cfg.checkpoint_path
        if path and os.path.exists(path):
            fault.emit_event("nonfinite_escalation", stage="restore",
                             epoch=int(epoch), streak=self._nf_streak)
            print_fn(f"# fault: non-finite streak persists — restoring "
                     f"from checkpoint {path}")
            self.restore(path)
        else:
            fault.emit_event("nonfinite_escalation", stage="no_checkpoint",
                             epoch=int(epoch), streak=self._nf_streak)
            print_fn("# fault: non-finite streak persists and no "
                     "checkpoint is available; continuing with skipped "
                     "updates")

    def evaluate(self) -> ops.PerfMetrics:
        # the jitted call until it returns to Python, like step_call
        with obs.span("eval_call"):
            return self._eval_step(self.params, self.x, self.labels,
                                   self.mask, self.gdata)

    def predict_logits(self):
        """Inference logits for every (padded, for SPMD) node row."""
        return self._logits_step(self.params, self.x, self.gdata)

    def run_epoch(self):
        cfg = self.config
        # what the host makes and transfers for this step alone
        with obs.span("step_args"):
            if self.epoch != 0 and self.epoch % cfg.decay_steps == 0:
                self.optimizer.alpha *= cfg.decay_rate  # gnn.cc:100-101
            step_key = jax.random.fold_in(self.key, self.epoch)
            alpha = jnp.float32(self.optimizer.alpha)
        # the jitted call until it returns to Python: a device gap after
        # this has closed is no longer the host's enqueueing
        with obs.span("step_call"):
            loss = self._run_step(step_key, alpha)
        self.epoch += 1
        return loss

    def train(self, print_fn=print):
        cfg = self.config
        num_edges = self.dataset.graph.num_edges
        self.epoch_times = []  # wall-clock per epoch (observability the
        start = self.epoch     # reference only had commented out, §5.1)
        # Profiler window from -profile-epochs (default 3:3 — up to 3
        # post-compile epochs); clamp into range so short runs still trace.
        p_off, p_cnt = cfg.profile_window()
        prof_start = start + min(p_off, max(cfg.num_epochs - 1, 0))
        prof_stop = min(prof_start + p_cnt, start + cfg.num_epochs)
        tracing = annotated = profiled = False
        loss = float("nan")
        rebalance_events = []
        peak_hbm = []
        peak_src = ""
        # Graceful-shutdown contract: SIGTERM/SIGINT only raise a flag;
        # the loop finishes the in-flight epoch, writes a final durable
        # checkpoint (the end-of-train save below), and exits cleanly.
        # Installable only on the main thread — elsewhere run unguarded.
        self._stop_signal = None

        def _on_stop(signum, frame):
            del frame
            self._stop_signal = signum

        installed = {}
        try:
            for s in (signal.SIGTERM, signal.SIGINT):
                installed[s] = signal.signal(s, _on_stop)
        except ValueError:
            installed = {}
        with obs.span("train", epochs=cfg.num_epochs) as sp_train:
            try:
                for epoch in range(start, start + cfg.num_epochs):
                    if cfg.profile_dir and epoch == prof_start:
                        # a profile without the program's spans is the
                        # case operators hit: annotate the window (the
                        # tracer's bridge alone, not the -obs metrics
                        # channel, which would change the compiled step)
                        annotated = obs.annotate(True)
                        jax.profiler.start_trace(cfg.profile_dir)
                        tracing = True
                    # the sync IS the measurement: an epoch "ends" when its
                    # result reaches the host, not when dispatch returns
                    with obs.span("epoch", epoch=epoch) as sp_epoch:
                        with obs.span("step_dispatch"):
                            loss = self.run_epoch()
                        with obs.span("device_sync"):
                            device_sync(loss)
                    self.epoch_times.append(sp_epoch.dur_s)
                    with obs.span("peak_hbm"):
                        hbm, peak_src = self._peak_hbm()
                    peak_hbm.append(hbm)
                    if self.balancer is not None:
                        self.balancer.telemetry.record_epoch(
                            epoch, self.epoch_times[-1], peak_hbm=hbm,
                            peak_hbm_source=peak_src)
                    if self._metrics is not None:       # -obs only
                        with obs.span("obs_epoch", epoch=epoch):
                            self._obs_epoch(epoch, sp_epoch.dur_s, loss,
                                            print_fn)
                    with obs.span("check_nonfinite"):
                        self._check_nonfinite(epoch, print_fn)
                    if tracing and epoch + 1 == prof_stop:
                        device_sync(self.params)
                        jax.profiler.stop_trace()
                        obs.annotate(annotated)
                        tracing, profiled = False, True
                        print_fn(f"# profiler trace written to "
                                 f"{cfg.profile_dir}")
                    if epoch % cfg.eval_every == 0:
                        with obs.span("eval", epoch=epoch):
                            m = self.evaluate()
                            with obs.span("eval_fetch"):
                                m = jax.device_get(m)
                        print_fn(format_metrics(epoch, m))
                    if (cfg.checkpoint_path and cfg.checkpoint_every and
                            (epoch + 1) % cfg.checkpoint_every == 0):
                        with obs.span("checkpoint", epoch=epoch):
                            self.save_checkpoint(cfg.checkpoint_path)
                    # Balance round at the epoch boundary (never after the
                    # last epoch — nothing left to speed up).
                    done = epoch + 1 - start
                    if (self.balancer is not None and done < cfg.num_epochs
                            and done % cfg.balance_every == 0):
                        with obs.span("balance", epoch=epoch):
                            ev = self.balancer.step(self, epoch + 1,
                                                    cfg.num_epochs - done)
                        if ev is not None:
                            rebalance_events.append(ev)
                            if cfg.verbose:
                                print_fn(
                                    f"# balance@{epoch + 1}: "
                                    f"{ev['action']} (pred gain "
                                    f"{ev['rel_gain'] * 100:.1f}%, "
                                    f"r2 {ev['r2']:.3f})")
                    # After the balance round, so an armed RetraceGuard
                    # sees a reshard's (cache-missing) rebuild as the
                    # violation it is.
                    with obs.span("retrace_boundary"):
                        _retrace.epoch_boundary(done)
                    if self._stop_signal is not None:
                        name = signal.Signals(self._stop_signal).name
                        print_fn(f"# fault: {name} received — epoch "
                                 f"{epoch} finished; checkpointing and "
                                 f"exiting cleanly")
                        break
            finally:
                # profiler-session leak fix: a crash mid-window must still
                # close the trace, or the next start_trace in the process
                # dies on the leaked session
                if tracing:
                    jax.profiler.stop_trace()
                    obs.annotate(annotated)
                for s, h in installed.items():
                    signal.signal(s, h)
            device_sync(self.params)
        dt = sp_train.dur_s
        if cfg.checkpoint_path:
            with obs.span("checkpoint"):
                self.save_checkpoint(cfg.checkpoint_path)
        if profiled:
            # the map the report joins the trace with: compiles of its own
            # (minutes at a cell's size), outside every span the loop
            # times and after the checkpoint is safe
            self._write_device_scopes(cfg.profile_dir, print_fn)
        if cfg.verbose and self.epoch_times:
            # steady-state epoch time: median of post-compile epochs
            steady = sorted(self.epoch_times[2:] or self.epoch_times)
            med = steady[len(steady) // 2]
            print_fn(f"# {cfg.num_epochs} epochs in {dt:.2f}s "
                     f"(median {med * 1e3:.1f} ms/epoch post-warmup, "
                     f"{num_edges / med / 1e6:.1f}M edges/s)")
        stats = TrainStats(
            epoch_times=list(self.epoch_times), total_s=dt,
            epochs=cfg.num_epochs, final_loss=float(device_sync(loss)),
            rebalance_events=rebalance_events,
            peak_hbm_bytes=peak_hbm, peak_hbm_source=peak_src)
        self._obs_finish(stats, print_fn)
        return stats

    # -- checkpoint/resume (absent from the reference, SURVEY.md §5.4) ----
    def _resume_extra(self):
        """JSON-able host-side state a crash-consistent resume needs
        beyond the param/optimizer arrays: the base PRNG key (so resumed
        dropout streams match the unkilled run exactly), the balancer's
        current cut, and the watchdog's learned EWMAs (a resumed run
        keeps its regression baselines instead of re-warming)."""
        import numpy as np
        extra = {"rng_key": [int(v) for v in np.asarray(self.key).ravel()],
                 "nonfinite_skips": int(self._nf_skips)}
        if self.watchdog is not None:
            extra["watchdog"] = self.watchdog.state_dict()
        bounds = getattr(getattr(self, "part", None), "bounds", None)
        if bounds is not None:
            extra["balance_bounds"] = [int(b) for b in np.asarray(bounds)]
        return extra

    def save_checkpoint(self, path: str, extra=None):
        from roc_tpu.train import checkpoint
        if extra is None:
            extra = self._resume_extra()
        # Params/opt state are replicated: every process holds the same
        # values, so only process 0 writes (P identical writers on shared
        # storage would be redundant work + a last-writer race); the barrier
        # keeps the others from racing ahead and e.g. resuming a checkpoint
        # that is still mid-rename.
        if jax.process_index() == 0:
            checkpoint.save(path, self.params, self.opt_state, self.epoch,
                            self.optimizer.alpha, extra=extra)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("roc_tpu_ckpt_saved")

    def restore(self, path: str):
        import numpy as np
        from roc_tpu.train import checkpoint
        (self.params, self.opt_state, self.epoch, self.optimizer.alpha,
         extra) = checkpoint.load(path, self.params, self.opt_state)
        if not extra:
            return
        if "rng_key" in extra:
            self.key = jnp.asarray(extra["rng_key"], jnp.uint32)
        if self.watchdog is not None and "watchdog" in extra:
            self.watchdog.load_state(extra["watchdog"])
        self._nf_skips = int(extra.get("nonfinite_skips", 0))
        bounds = extra.get("balance_bounds")
        cur = getattr(getattr(self, "part", None), "bounds", None)
        if bounds is not None and cur is not None and hasattr(self, "reshard") \
                and not np.array_equal(np.asarray(bounds), np.asarray(cur)):
            # re-apply the balancer's last committed cut so the resumed
            # partition matches the one the checkpointed run trained on
            self.reshard(np.asarray(bounds, np.int64))


class Trainer(BaseTrainer):
    """Single-device full-graph trainer."""

    def _setup(self):
        ds, model = self.dataset, self.model
        backend = self._effective_backend()
        self.gdata = dense_graph_data(
            ds.graph, backend, self.config.aggregate_precision,
            gat_backend=self._gat_backend(),
            storage_dtype="bf16" if self.config.bf16_storage else "fp32",
            autotune=self.config.autotune,
            attention=attention_kind(model) or "gat")
        with obs.span("place_data", what="nodes"):
            self.x = jnp.asarray(ds.features, self.dtype)
            self.labels = jnp.asarray(ds.onehot_labels(), jnp.float32)
            self.mask = jnp.asarray(ds.mask, jnp.int32)
        with obs.span("init_params"):
            self.params = model.init_params(self.key)
            self.opt_state = self.optimizer.init(self.params)
        self.num_nodes = ds.graph.num_nodes
        with obs.span("mem_plan"):
            self._resolve_mem_plan()
        with obs.span("step_build"):
            self._build_steps()

    def _build_steps(self):
        """The jitted train, eval and logits steps (closures over the
        model, the loss and the node count; nothing compiles here)."""
        model, n = self.model, self.num_nodes
        loss_fn = self._loss_fn()
        obs_on = self.config.obs
        if obs_on:
            from roc_tpu.obs import channel as obs_channel

        @jax.jit
        def train_step(params, opt_state, x, labels, mask, gdata, key, alpha,
                       gscale):
            _retrace.note_trace("train_step")
            gctx = make_gctx(gdata, n)
            loss, grads = jax.value_and_grad(loss_fn)(
                params, x, labels, mask, gctx, key=key, train=True)
            # gscale is 1.0 on every healthy step (an exact multiply —
            # bitwise no-op); the chaos harness feeds NaN to exercise the
            # guard.  Same shape/dtype either way: no retrace.
            loss = loss * gscale
            grads = jax.tree.map(lambda g: g * gscale, grads)
            params, opt_state, nonfinite, gnorm = fault.guarded_update(
                self.optimizer, params, grads, opt_state, alpha, loss=loss)
            if not obs_on:
                return params, opt_state, loss, nonfinite
            # in-graph metrics channel (obs/channel.py): pure functions of
            # values already in the program — no syncs, no collectives
            metrics = {
                "grad_norm": gnorm,
                "param_norm": obs_channel.global_norm(params),
                # single device: nothing crosses a wire
                "wire_bytes": jnp.float32(0.0),
                "edges": jnp.sum(gdata.in_degree).astype(jnp.int32)[None],
            }
            return params, opt_state, loss, nonfinite, metrics

        @jax.jit
        def eval_step(params, x, labels, mask, gdata):
            _retrace.note_trace("eval_step")
            gctx = make_gctx(gdata, n)
            logits = model.apply(params, x, gctx, train=False)
            return ops.perf_metrics(logits, labels, mask)

        @jax.jit
        def logits_step(params, x, gdata):
            _retrace.note_trace("logits_step")
            return model.apply(params, x, make_gctx(gdata, n),
                               train=False)

        self._train_step = train_step
        self._eval_step = eval_step
        self._logits_step = logits_step


def make_trainer(config: Config, dataset: Dataset, model: Model) -> BaseTrainer:
    """The one place that picks Trainer vs SpmdTrainer.  Both the CLI's
    `-check-sharding` and `-analyze` paths, the audit matrix, and bench.py
    go through here so a trainer (and its partition + compiled steps) is
    built exactly once and reused."""
    if config.stream:
        from roc_tpu.stream.executor import StreamTrainer
        return StreamTrainer(config, dataset, model)
    budget = config.stream_budget_bytes()
    if budget:
        from roc_tpu.stream import incore_resident_bytes
        need = incore_resident_bytes(dataset)
        if need > budget:
            # the out-of-core gate: refuse to build an in-core trainer for
            # a graph whose placed data alone exceeds the device budget
            def _fmt(b):
                return (f"{b / 2**20:.0f} MiB" if b >= 2**20
                        else f"{b / 2**10:.0f} KiB")
            raise SystemExit(
                f"error: graph needs ~{_fmt(need)} device-resident "
                f"but -stream-budget is {_fmt(budget)}; rerun "
                f"with -stream to rotate shards through host memory "
                f"(add -stream-spill DIR when even host memory cannot "
                f"hold the boundary stores, and -bf16-storage to halve "
                f"the streamed bytes)")
    if config.num_parts > 1:
        from roc_tpu.parallel.spmd import SpmdTrainer
        return SpmdTrainer(config, dataset, model)
    return Trainer(config, dataset, model)
