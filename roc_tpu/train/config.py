"""Run configuration + CLI, mirroring the reference's flags.

Reference parse_input_args (gnn.cc:114-179) and defaults (gnn.cc:31-40):
  -e / -epoch N        epochs (default 1)
  -lr F                learning rate (default 0.01)
  -dropout F           dropout rate (default 0.5)
  -decay / -wd F       weight decay (default 0.05)
  -decay-rate F        LR decay factor (default 1.0)
  -decay-step / -ds N  LR decay interval in epochs (default 100)
  -seed N              RNG seed (default 1)
  -file S              dataset prefix (ROC on-disk format)
  -layers H0-H1-...    layer widths incl. input and classes (e.g. 602-256-41)
  -ng / -ll:gpu N      devices per machine → we take -parts (total shards)
  -v                   verbose

The reference double-binds `-dr` to both dropout and decay-rate
(gnn.cc:138-152) — a latent CLI bug we do NOT reproduce; use the long names.
TPU-only additions: -parts, -dataset (synthetic registry name), -aggr,
-model, -ckpt/-resume, -bf16.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import List, Optional

_SIZE_SUFFIX = {"k": 2 ** 10, "m": 2 ** 20, "g": 2 ** 30, "t": 2 ** 40}


def parse_size(s: str) -> int:
    """Byte-size spec with binary suffixes: '6g', '512m', '8589934592'.
    Empty string means "no budget" (0).  SystemExit on malformed input so
    CLI/env mistakes fail loudly, matching the balance env handling."""
    s = (s or "").strip().lower()
    if not s:
        return 0
    mult = 1
    if s[-1] in _SIZE_SUFFIX:
        mult = _SIZE_SUFFIX[s[-1]]
        s = s[:-1]
    try:
        return int(float(s) * mult)
    except ValueError:
        raise SystemExit(f"bad byte-size spec {s!r} "
                         "(want e.g. 6g, 512m, 8589934592)")


@dataclasses.dataclass
class Config:
    filename: str = ""            # ROC-format dataset prefix (-file)
    dataset: str = ""             # synthetic registry name (TPU addition)
    layers: List[int] = dataclasses.field(default_factory=list)
    num_epochs: int = 1
    learning_rate: float = 0.01
    weight_decay: float = 0.05
    dropout_rate: float = 0.5
    decay_rate: float = 1.0
    decay_steps: int = 100
    seed: int = 1
    num_parts: int = 1            # total shards (== mesh size when > 1)
    model: str = "gcn"            # gcn|sage|gin|gat|gatv2|tconv|gcnii
    heads: int = 8                # attention heads (gat, gatv2, tconv)
    aggr: str = ""                # "" = model default; sum|avg|max|min
    aggregate_backend: str = "auto"  # auto | xla | matmul | pallas(=binned) | binned
    aggregate_precision: str = "fast"  # fast (default): features take one
                                  # designed bf16 rounding at aggregation
                                  # input — golden curves within +-1 sample
                                  # of fp32, docs/GOLDEN.md; exact: fp32 end
                                  # to end on BOTH plan backends (matmul
                                  # highest-precision dots; binned fp32
                                  # staging + 3-way split dots).  Policy
                                  # argument: BASELINE.md §precision.
    verbose: bool = False
    eval_every: int = 5           # reference evaluates every 5 epochs (gnn.cc:107)
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 0     # 0 = disabled
    resume: bool = False
    use_bf16: bool = False        # opt-in activation bf16 (SURVEY §7 non-goal note)
    bf16_storage: bool = False    # bf16 STORAGE / fp32 accumulation on the
                                  # memory-bound hot paths: flat-schedule
                                  # staging moves bf16 (16-row units) and
                                  # ICI feature exchanges (halo/allgather/
                                  # ring) go over the wire as bf16, upcast
                                  # at the aggregation boundary.  Compute
                                  # and activations stay fp32 (unlike
                                  # -bf16, which casts activations).
    bf16_rounding: str = "nearest"  # bf16 downcast mode for the exchange
                                  # wire: nearest | stochastic (unbiased
                                  # SR for parity-sensitive runs)
    bf16_exchange: str = "plain"  # plain: one bf16 term (half the bytes) |
                                  # compensated: (hi, lo) bf16 pair — fp32
                                  # bytes, parity control for the pipeline
    autotune: bool = False        # geometry autotuner (roc_tpu/tune): sweep
                                  # this graph's kernel-config space before
                                  # the plan builds and persist the winners
                                  # in the content-keyed tuned store that
                                  # choose_geometry / build_binned_plan
                                  # consult.  Surrogate (cost-model) trials
                                  # off-hardware, real timed trials on TPU.
                                  # Kill switch for consumption:
                                  # ROC_NO_TUNED=1
    lazy_load: bool = False       # memmap features / defer one-hot labels
                                  # (sharded host loading for huge graphs)
    halo: bool = True             # v1 halo exchange vs v0 all_gather
    exchange: str = ""            # halo | allgather | ring (empty: derive
                                  # from `halo`; ring = ppermute rotation,
                                  # memory-bounded — parallel/ring.py)
    check_sharding: bool = False  # validate sharded == single-device first
    analyze: bool = False         # static audit before + retrace report
                                  # after the run (roc_tpu/analysis/):
                                  # collective/f64 audit of the lowered
                                  # steps, budget diff when the config has
                                  # a budgets.json entry, RetraceGuard in
                                  # record mode around train()
    profile_dir: str = ""         # write a jax.profiler trace (window set
                                  # by -profile-epochs; default 3:3)
    profile_epochs: str = ""      # profiler window "START:COUNT" relative
                                  # to this call's first epoch ("" = "3:3",
                                  # the historical 3-post-compile-epochs
                                  # default; only meaningful with -profile)
    obs: bool = False             # unified runtime observability
                                  # (roc_tpu/obs): record host spans, ride
                                  # loss/grad-norm/wire-byte metrics on the
                                  # jitted step's outputs (fetched once per
                                  # epoch — zero host syncs in jit), run
                                  # the perf watchdog, export trace.json +
                                  # metrics.jsonl under -obs-dir
    obs_dir: str = ""             # obs artifact dir ("" with -obs on ->
                                  # "roc_obs"; trace.json / metrics.jsonl /
                                  # metrics.prom)
    multihost: bool = False       # jax.distributed.initialize() before run
    perhost_load: bool = False    # each process reads only its parts' .lux
                                  # byte ranges (pod-scale; needs -file)
    edge_shard: object = "auto"   # exactly-equal edge blocks + psum_scatter
                                  # (skew-proof aggregation; sum/avg only).
                                  # "auto": on when the partitioner's
                                  # padded-max tax exceeds ~30% (docs/PERF.md
                                  # rule of thumb); True/"on", False/"off"
                                  # force it
    reorder: object = "off"       # RCM locality pass before partitioning
                                  # (graph/reorder.py — concentrates the
                                  # (block, bin) cells the TPU tiled
                                  # kernels pay for; no reference
                                  # counterpart).  "off" | "on"/True |
                                  # "auto" (keep only on a measured >=10%
                                  # padded-row reduction)
    balance_every: int = 0        # online cost-model load balancer cadence
                                  # in epochs (roc_tpu/balance/ — ROC's
                                  # learned repartitioner); 0 = off.  SPMD
                                  # vertex modes only; Trainer/edge-shard/
                                  # ring/perhost runs ignore it with a note
    balance_min_gain: float = 0.05  # hysteresis: reshard only when the
                                  # predicted max-part time drops by at
                                  # least this fraction
    balance_trace: str = ""       # JSONL telemetry trace path ("" = none)
    mem_plan: str = "keep"        # activation-memory plan (roc_tpu/memory):
                                  # keep (default; no remat — byte-identical
                                  # to the pre-planner programs) | auto (DP
                                  # under -mem-budget) | remat (every layer)
    mem_budget: str = ""          # per-device HBM budget for -mem-plan auto
                                  # (k/m/g/t suffixes; "" = the device's
                                  # reported bytes_limit, or unbounded when
                                  # the backend doesn't report one)
    stream: bool = False          # out-of-core host-streaming executor
                                  # (roc_tpu/stream): shards live in host
                                  # memory and rotate through a fixed set
                                  # of frozen padded device slots, layer-k
                                  # compute of shard i overlapped with the
                                  # prefetch of shard i+1.  Requires
                                  # -parts >= 2; makes the memory planner's
                                  # OFFLOAD verdict executable
    stream_slots: int = 2         # prefetch ring depth (device slots in
                                  # flight; 2 = classic double buffering)
    stream_budget: str = ""       # aggregate device-memory budget the
                                  # in-core path is held to (k/m/g/t
                                  # suffixes).  Without -stream, a graph
                                  # whose resident bytes exceed it refuses
                                  # to run in-core — the out-of-core gate
    stream_spill: str = ""        # spill directory for the third rotation
                                  # tier: segment-boundary activation and
                                  # cotangent stores memory-map to CRC'd
                                  # files here (NVMe-class path) instead of
                                  # host RAM, so host memory only holds the
                                  # graph-shaped arrays.  Requires -stream
    serve_batch: int = 64         # serving microbatch cap (roc_tpu/serve):
                                  # a queue window drains when this many
                                  # queries accumulate, and the padded
                                  # bucket ladder tops out here — larger
                                  # batch = better QPS, more padding waste
                                  # on sparse streams
    serve_wait_ms: float = 2.0    # max ms a serving window stays open
                                  # waiting to fill before draining — the
                                  # latency half of the batch/wait knob
                                  # pair; 0 drains after every request
    serve_queue_max: int = 4096   # serve overload policy: max pending
                                  # requests before submit() sheds with a
                                  # typed Overloaded error (bounded queue
                                  # memory under overload); 0 = unbounded
    fault: str = ""               # chaos harness spec (roc_tpu/fault):
                                  # seeded deterministic fault injection
                                  # at named sites, e.g.
                                  # "seed=3,ring.fetch=2,lux.read@0.1,
                                  # retries=0".  Empty = disarmed (every
                                  # fault.point is a no-op)

    def __post_init__(self):
        # ROC_BALANCE* env overrides so driverless entry points (bench.py,
        # test fixtures) can switch the balancer on without plumbing flags.
        import os
        env = os.environ
        try:
            if "ROC_BALANCE_EVERY" in env:
                self.balance_every = int(env["ROC_BALANCE_EVERY"])
            if "ROC_BALANCE_MIN_GAIN" in env:
                self.balance_min_gain = float(env["ROC_BALANCE_MIN_GAIN"])
        except ValueError:
            raise SystemExit("ROC_BALANCE_EVERY / ROC_BALANCE_MIN_GAIN "
                             "must be numeric")
        if env.get("ROC_BALANCE_TRACE"):
            self.balance_trace = env["ROC_BALANCE_TRACE"]
        # ROC_MEM_* mirror -mem-plan / -mem-budget for driverless entry
        # points (bench.py, audit fixtures).
        if env.get("ROC_MEM_PLAN"):
            self.mem_plan = env["ROC_MEM_PLAN"]
        if self.mem_plan not in ("keep", "auto", "remat"):
            raise SystemExit(f"bad mem_plan {self.mem_plan!r} "
                             "(keep|auto|remat)")
        if env.get("ROC_MEM_BUDGET"):
            self.mem_budget = env["ROC_MEM_BUDGET"]
        parse_size(self.mem_budget)  # validate eagerly (SystemExit if bad)
        # ROC_STREAM* mirror -stream/-stream-slots/-stream-budget for
        # driverless entry points (bench.py, out-of-core test fixtures).
        if env.get("ROC_STREAM"):
            self.stream = env["ROC_STREAM"] == "1"
        try:
            if "ROC_STREAM_SLOTS" in env:
                self.stream_slots = int(env["ROC_STREAM_SLOTS"])
        except ValueError:
            raise SystemExit("ROC_STREAM_SLOTS must be an integer")
        if env.get("ROC_STREAM_BUDGET"):
            self.stream_budget = env["ROC_STREAM_BUDGET"]
        parse_size(self.stream_budget)  # validate eagerly
        if env.get("ROC_STREAM_SPILL"):
            self.stream_spill = env["ROC_STREAM_SPILL"]
        if self.stream_slots < 2:
            raise SystemExit(f"stream_slots={self.stream_slots}: the "
                             "prefetch ring needs >= 2 slots (double "
                             "buffering is the point)")
        if self.stream_spill and not self.stream:
            raise SystemExit("error: -stream-spill is a tier of the "
                             "streaming executor; it requires -stream")
        # ROC_BF16_* mirror -bf16-storage/-bf16-rounding/-bf16-exchange for
        # driverless entry points (bench.py, hw_revalidate A/B loops).
        if env.get("ROC_BF16_STORAGE"):
            self.bf16_storage = env["ROC_BF16_STORAGE"] == "1"
        if env.get("ROC_BF16_ROUNDING"):
            self.bf16_rounding = env["ROC_BF16_ROUNDING"]
        if env.get("ROC_BF16_EXCHANGE"):
            self.bf16_exchange = env["ROC_BF16_EXCHANGE"]
        if self.bf16_rounding not in ("nearest", "stochastic"):
            raise SystemExit(f"bad bf16_rounding {self.bf16_rounding!r} "
                             "(nearest|stochastic)")
        if self.bf16_exchange not in ("plain", "compensated"):
            raise SystemExit(f"bad bf16_exchange {self.bf16_exchange!r} "
                             "(plain|compensated)")
        # ROC_AUTOTUNE mirrors -autotune for driverless entry points
        # (bench.py, hw_revalidate's sweep leg); ROC_NO_TUNED stays the
        # runtime kill switch on tuned-store CONSUMPTION.
        if env.get("ROC_AUTOTUNE"):
            self.autotune = env["ROC_AUTOTUNE"] == "1"
        if self.bf16_storage and self.aggregate_precision == "exact":
            # the binned flat bf16 unit and the bf16 wire both round where
            # "exact" promises fp32 end to end — refuse the contradiction
            raise SystemExit("-bf16-storage is incompatible with "
                             "-aggr-precision exact (bf16 storage rounds "
                             "features; exact promises fp32 end to end)")
        # ROC_OBS / ROC_OBS_DIR mirror -obs / -obs-dir for driverless entry
        # points (bench.py, audit/test fixtures) — same env the span tracer
        # reads at import, so cfg.obs and tracer state agree.
        if env.get("ROC_OBS"):
            self.obs = env["ROC_OBS"] == "1"
        if env.get("ROC_OBS_DIR"):
            self.obs_dir = env["ROC_OBS_DIR"]
        if self.obs and not self.obs_dir:
            self.obs_dir = "roc_obs"
        if env.get("ROC_PROFILE_EPOCHS"):
            self.profile_epochs = env["ROC_PROFILE_EPOCHS"]
        self.profile_window()  # validate eagerly (SystemExit if bad)
        # ROC_SERVE_* mirror -serve-batch/-serve-wait-ms for driverless
        # entry points (serve_bench.py, preflight's serve smoke).
        try:
            if "ROC_SERVE_BATCH" in env:
                self.serve_batch = int(env["ROC_SERVE_BATCH"])
            if "ROC_SERVE_WAIT_MS" in env:
                self.serve_wait_ms = float(env["ROC_SERVE_WAIT_MS"])
        except ValueError:
            raise SystemExit("ROC_SERVE_BATCH must be an integer and "
                             "ROC_SERVE_WAIT_MS numeric")
        if self.serve_batch < 1:
            raise SystemExit(f"serve_batch={self.serve_batch}: the serving "
                             "window must admit at least one query")
        if self.serve_wait_ms < 0:
            raise SystemExit(f"serve_wait_ms={self.serve_wait_ms} must be "
                             ">= 0 (0 drains after every request)")
        try:
            if "ROC_SERVE_QUEUE_MAX" in env:
                self.serve_queue_max = int(env["ROC_SERVE_QUEUE_MAX"])
        except ValueError:
            raise SystemExit("ROC_SERVE_QUEUE_MAX must be an integer")
        if self.serve_queue_max < 0:
            raise SystemExit(f"serve_queue_max={self.serve_queue_max} must "
                             "be >= 0 (0 disables the depth cap)")
        # ROC_FAULT mirrors -fault (the fault harness also reads the env
        # directly at import so driverless entry points arm without a
        # Config); validate the spec eagerly so a typo'd chaos leg dies
        # at startup, not mid-run.
        if env.get("ROC_FAULT"):
            self.fault = env["ROC_FAULT"]
        if self.fault:
            from roc_tpu.fault import inject as _fault_inject
            try:
                _fault_inject.parse_spec(self.fault)
            except ValueError as e:
                raise SystemExit(f"bad -fault spec {self.fault!r}: {e}")

    def mem_budget_bytes(self) -> int:
        """-mem-budget in bytes (0 = unset; driver falls back to the
        device's reported HBM limit)."""
        return parse_size(self.mem_budget)

    def stream_budget_bytes(self) -> int:
        """-stream-budget in bytes (0 = unset; no in-core gate)."""
        return parse_size(self.stream_budget)

    def exchange_mode(self) -> str:
        """Effective exchange mode ('halo' | 'allgather' | 'ring')."""
        return self.exchange or ("halo" if self.halo else "allgather")

    def profile_window(self) -> tuple:
        """-profile-epochs "START:COUNT" -> (start_offset, count).  START
        is relative to the train() call's first epoch (so resumes keep the
        post-compile intent); default 3:3 is the historical hard-coded
        window.  SystemExit on malformed input, like every knob here."""
        spec = self.profile_epochs or "3:3"
        try:
            start_s, count_s = spec.split(":")
            start, count = int(start_s), int(count_s)
            if start < 0 or count < 1:
                raise ValueError
        except ValueError:
            raise SystemExit(f"bad profile_epochs {spec!r} "
                             "(want START:COUNT, e.g. 0:1 or 3:3)")
        return start, count


def parse_args(argv: List[str]) -> Config:
    p = argparse.ArgumentParser(
        prog="roc_tpu", description="TPU-native full-graph GNN training")
    p.add_argument("-file", dest="filename", default="")
    p.add_argument("-dataset", default="")
    p.add_argument("-layers", default="",
                   help="dash-separated widths, e.g. 602-256-41")
    p.add_argument("-e", "-epoch", dest="num_epochs", type=int, default=1)
    p.add_argument("-lr", dest="learning_rate", type=float, default=0.01)
    p.add_argument("-dropout", dest="dropout_rate", type=float, default=0.5)
    p.add_argument("-decay", "-wd", dest="weight_decay", type=float, default=0.05)
    p.add_argument("-decay-rate", dest="decay_rate", type=float, default=1.0)
    p.add_argument("-decay-step", "-ds", dest="decay_steps", type=int, default=100)
    p.add_argument("-seed", type=int, default=1)
    p.add_argument("-parts", "-ng", "-ll:gpu", dest="num_parts", type=int,
                   default=1)
    p.add_argument("-model", default="gcn",
                   choices=["gcn", "sage", "gin", "gat", "gatv2", "tconv",
                            "gcnii"],
                   help="gcn | sage | gin | gat | gatv2 (dynamic attention) "
                        "| tconv (graph transformer) "
                        "| gcnii (deep GCN: a hidden -layers entry is one "
                        "GCNII layer, all equal); models.build_model")
    p.add_argument("-heads", type=int, default=8)
    p.add_argument("-aggr", default="",
                   choices=["", "sum", "avg", "max", "min"])
    p.add_argument("-aggr-precision", dest="aggregate_precision",
                   default="fast", choices=["exact", "fast"])
    p.add_argument("-aggr-backend", dest="aggregate_backend", default="auto",
                   choices=["auto", "xla", "matmul", "pallas", "binned"])
    p.add_argument("-v", dest="verbose", action="store_true")
    p.add_argument("-eval-every", dest="eval_every", type=int, default=5)
    p.add_argument("-ckpt", dest="checkpoint_path", default=None)
    p.add_argument("-ckpt-every", dest="checkpoint_every", type=int, default=0)
    p.add_argument("-resume", action="store_true")
    p.add_argument("-bf16", dest="use_bf16", action="store_true")
    p.add_argument("-bf16-storage", dest="bf16_storage",
                   action="store_true")
    p.add_argument("-bf16-rounding", dest="bf16_rounding",
                   default="nearest", choices=["nearest", "stochastic"])
    p.add_argument("-bf16-exchange", dest="bf16_exchange",
                   default="plain", choices=["plain", "compensated"])
    p.add_argument("-autotune", dest="autotune", action="store_true",
                   help="sweep the kernel-config space for this graph and "
                        "persist the winners in the tuned store "
                        "(roc_tpu/tune) before building plans")
    p.add_argument("-lazy", dest="lazy_load", action="store_true")
    p.add_argument("-no-halo", dest="halo", action="store_false")
    p.add_argument("-exchange", dest="exchange", default="",
                   choices=["", "halo", "allgather", "ring"])
    p.add_argument("-check-sharding", dest="check_sharding",
                   action="store_true")
    p.add_argument("-analyze", dest="analyze", action="store_true")
    p.add_argument("-profile", dest="profile_dir", default="")
    p.add_argument("-profile-epochs", dest="profile_epochs", default="",
                   help="profiler window START:COUNT relative to the first "
                        "epoch (default 3:3)")
    p.add_argument("-obs", action="store_true",
                   help="runtime observability: host spans + in-graph "
                        "metrics + perf watchdog (roc_tpu/obs)")
    p.add_argument("-obs-dir", dest="obs_dir", default="",
                   help="obs artifact dir (default roc_obs)")
    p.add_argument("-multihost", action="store_true")
    p.add_argument("-perhost", dest="perhost_load", action="store_true")
    p.add_argument("-edge-shard", dest="edge_shard", nargs="?", const="on",
                   default="auto", choices=["on", "off", "auto"])
    p.add_argument("-reorder", nargs="?", const="on", default="off",
                   choices=["on", "off", "auto"])
    p.add_argument("-balance-every", dest="balance_every", type=int,
                   default=0)
    p.add_argument("-balance-min-gain", dest="balance_min_gain", type=float,
                   default=0.05)
    p.add_argument("-balance-trace", dest="balance_trace", default="")
    p.add_argument("-mem-plan", dest="mem_plan", default="keep",
                   choices=["keep", "auto", "remat"])
    p.add_argument("-mem-budget", dest="mem_budget", default="",
                   help="per-device HBM budget for -mem-plan auto "
                        "(e.g. 6g, 512m)")
    p.add_argument("-stream", action="store_true",
                   help="out-of-core host-streaming executor: shards "
                        "rotate through frozen device slots with "
                        "double-buffered prefetch (roc_tpu/stream)")
    p.add_argument("-stream-slots", dest="stream_slots", type=int,
                   default=2, help="prefetch ring depth (default 2)")
    p.add_argument("-stream-budget", dest="stream_budget", default="",
                   help="aggregate device-memory budget the in-core path "
                        "is held to (e.g. 8g); larger graphs must -stream")
    p.add_argument("-stream-spill", dest="stream_spill", default="",
                   help="spill directory for boundary stores: the third "
                        "rotation tier (NVMe memmap) when even host "
                        "memory cannot hold the boundary activations")
    p.add_argument("-serve-batch", dest="serve_batch", type=int, default=64,
                   help="serving microbatch cap: window drains at this "
                        "many queries; bucket ladder tops out here")
    p.add_argument("-serve-wait-ms", dest="serve_wait_ms", type=float,
                   default=2.0, help="max ms a serving window waits to "
                        "fill before draining (0 = drain per request)")
    p.add_argument("-serve-queue-max", dest="serve_queue_max", type=int,
                   default=4096, help="max pending serve requests before "
                        "submits shed with Overloaded (0 = unbounded)")
    p.add_argument("-fault", default="",
                   help="chaos spec (roc_tpu/fault), e.g. "
                        "'seed=3,ring.fetch=2,step.nan=1'; empty = off")
    ns = p.parse_args(argv)
    cfg = Config(**{f.name: getattr(ns, f.name) if f.name != "layers" else []
                    for f in dataclasses.fields(Config)})
    if ns.layers:
        cfg.layers = [int(x) for x in ns.layers.split("-")]  # gnn.cc:168-177
    return cfg
