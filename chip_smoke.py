"""chip_smoke.py — the quickest proof the trainer still starts on the chip.

    python chip_smoke.py                 on a machine with a TPU
    python chip_smoke.py --rehearse-cpu  tiny CPU walk through the same code

One process drives `python -m roc_tpu`'s own path — parse_args ->
datasets.get -> build_model -> make_trainer -> train() — at the full width
of the one configuration the repo has measured (2-layer GCN 602-256-41 on
the Reddit shape), with random weights from the seed:

  Leg A, one device.  Checks that the backend is a TPU, that `auto`
    resolved to the binned Pallas kernels (geometries printed), that the
    lowered step holds Mosaic custom calls, that six epochs give finite,
    falling losses with no skipped update and no trace after the first
    epoch, and that two epochs on `-aggr-backend xla` from the same seed
    agree with the kernels' losses.
  Leg B, four devices (`-parts 4`, SpmdTrainer, halo exchange).  Checks one
    part per distinct device, one shard of every sharded operand per
    device, live bytes on all four, the halo all-to-all and the gradient
    all-reduce in the compiled step, the same six-epoch run, and — with
    dropout off, because each device draws its own dropout mask — two
    epochs that track the single-device kernels' losses.  On a smaller
    machine it prints "not run: N devices" — `-parts 4` there would
    silently stack four shards on what is there.

With no TPU the script exits 2 and prints no result.  Otherwise standard
output ends with two JSON lines.  The summary, `{"leg_a": ..., "leg_b": ...,
"claim": null}`: this script proves the program runs; it claims no speed.
Then, last, the result, exactly `{"ok": true, "device": {"platform": ...,
"kind": ..., "count": N}}` with the device as JAX reports it.  A failed
check or a raised error gives `"ok": false` and exit code 1.  The rehearsal
names itself on every line and reports `"ok": false`.
"""

from __future__ import annotations

import gc
import json
import math
import os
import sys
import traceback

REDDIT = ["-dataset", "reddit", "-layers", "602-256-41", "-lr", "0.01",
          "-decay", "0.0001", "-dropout", "0.5"]
# The rehearsal's stand-in: small enough for the Pallas interpreter.
REHEARSAL = ["-dataset", "pubmed", "-layers", "500-64-3", "-lr", "0.01",
             "-decay", "0.0001", "-dropout", "0.5"]
EPOCHS = 6
PARITY_EPOCHS = 2
KERNEL_VS_XLA_RTOL = 5e-3    # binned/fast vs xla (verify skill, round 2)
SHARDED_VS_SINGLE_RTOL = 2e-3   # tests/test_parallel.py, sharded vs single
LEG_B_PARTS = 4

class Failed(Exception):
    """A check did not hold; the message says which."""


class CompileCounter:
    """Backend compile seconds and persistent-cache traffic, from
    jax.monitoring: every compile request that consults the cache, and
    the requests it answered."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.requests, self.hits

    def since(self, mark) -> str:
        s0, r0, h0 = mark
        hits = self.hits - h0
        return (f"backend compile {self.seconds - s0:.1f}s ("
                f"{self.requests - r0 - hits} program(s) compiled, {hits} "
                f"loaded from the persistent cache)")


def geometries(gdata) -> str:
    """The forward and transposed-backward Geometry of the binned plan set
    the trainer built ("none" on any other backend)."""
    p = getattr(gdata, "plans", None)
    if not hasattr(getattr(p, "fwd", None), "geom"):
        return "none"
    return f"plans: fwd={tuple(p.fwd.geom)} bwd={tuple(p.bwd.geom)}"


def one_part_per_device(trainer, parts: int) -> bool:
    """`-parts N` on fewer than N devices does not fail, it overcommits
    (parallel/mesh.py): k = N / devices shard blocks per device.  A leg
    that is about N chips must refuse that."""
    return len(set(trainer.mesh.devices.flat)) == parts and trainer.k == 1


def rel_delta(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


class Smoke:
    """One run of the script: how it talks, what it has built so far."""

    def __init__(self, rehearse: bool, stamp: str):
        self.rehearse = rehearse
        self.stamp = stamp          # device banner for lines with a time
        self.tag = "[REHEARSAL cpu] " if rehearse else ""
        self.base = REHEARSAL if rehearse else REDDIT
        # `auto` answers xla off the chip: the rehearsal asks for the
        # kernels by name (they run in the Pallas interpreter)
        self.kernels = ["-aggr-backend", "binned"] if rehearse else []
        self.compiles = CompileCounter()
        self._datasets = {}         # (name, seed) -> Dataset, built once

    def say(self, msg: str) -> None:
        print(f"{self.tag}{msg}", flush=True)

    def check(self, ok: bool, what: str) -> None:
        self.say(f"  [{'ok' if ok else 'FAILED'}] {what}")
        if not ok:
            raise Failed(what)

    def build(self, argv):
        """The CLI's own route to a trainer; set-up seconds printed apart
        from any epoch."""
        from roc_tpu import obs
        from roc_tpu.graph import datasets
        from roc_tpu.models import build_model
        from roc_tpu.train.config import parse_args
        from roc_tpu.train.driver import make_trainer

        cfg = parse_args(argv)
        key = (cfg.dataset, cfg.seed)
        with obs.span("smoke_dataset") as sp_ds:
            if key not in self._datasets:
                self._datasets[key] = datasets.get(cfg.dataset, seed=cfg.seed)
        ds = self._datasets[key]
        model = build_model(cfg.model, cfg.layers, cfg.dropout_rate,
                            cfg.aggr, heads=cfg.heads)
        mark = self.compiles.mark()
        with obs.span("smoke_build") as sp_tr:
            trainer = make_trainer(cfg, ds, model)
        self.say(f"  {' '.join(argv)}")
        self.say(f"  set-up: dataset {sp_ds.dur_s:.1f}s "
                 f"({ds.graph.num_nodes} nodes, {ds.graph.num_edges} edges), "
                 f"trainer {sp_tr.dur_s:.1f}s (partition + plans + "
                 f"placement; {self.compiles.since(mark)}) [{self.stamp}]")
        return cfg, trainer

    def train_and_check(self, trainer) -> list:
        """train() for EPOCHS epochs under an armed RetraceGuard; returns
        the per-epoch training losses."""
        import numpy as np

        from roc_tpu import obs
        from roc_tpu.analysis import RetraceGuard

        losses = []
        run_epoch = trainer.run_epoch

        def recording_run_epoch():
            losses.append(run_epoch())
            return losses[-1]

        trainer.run_epoch = recording_run_epoch
        mark = self.compiles.mark()
        with RetraceGuard(warmup=1, on_violation="record") as guard:
            with obs.span("smoke_train") as sp:
                stats = trainer.train(print_fn=self.say)
        losses = [float(np.asarray(v)) for v in losses]
        t = stats.epoch_times
        later = sorted(t[1:])
        self.say(f"  train(): {sp.dur_s:.1f}s for {len(t)} epochs and 2 "
                 f"evals, of it {self.compiles.since(mark)}; first epoch "
                 f"{t[0]:.2f}s, later epochs median "
                 f"{later[len(later) // 2] * 1e3:.0f} ms [{self.stamp}]")
        self.say(f"  losses: {' '.join(f'{v:.4f}' for v in losses)}")
        self.check(len(losses) == EPOCHS
                   and all(math.isfinite(v) for v in losses),
                   f"{EPOCHS} epochs, every loss finite")
        self.check(trainer._nf_skips == 0,
                   "no update skipped by the non-finite guard")
        self.check(losses[-1] < losses[0],
                   f"loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")
        self.check(not guard.violations,
                   f"zero traces after the first epoch ({guard.snapshot()})")
        return losses

    def first_losses(self, argv, backend: str) -> list:
        """PARITY_EPOCHS training losses of a fresh trainer on ``argv``."""
        from roc_tpu import obs

        _, tr = self.build(argv)
        self.check(tr.gdata.backend == backend, f"backend is {backend}")
        mark = self.compiles.mark()
        with obs.span("smoke_parity") as sp:
            out = [float(tr.run_epoch()) for _ in range(PARITY_EPOCHS)]
        self.say(f"  {PARITY_EPOCHS} epochs in {sp.dur_s:.1f}s, of it "
                 f"{self.compiles.since(mark)} [{self.stamp}]")
        del tr
        gc.collect()
        return out

    def check_close(self, got, want, rtol: float, names) -> None:
        for i, (g, w) in enumerate(zip(got, want)):
            self.check(rel_delta(g, w) <= rtol,
                       f"epoch {i}: {names[0]} {g:.4f} vs {names[1]} "
                       f"{w:.4f} (delta {rel_delta(g, w):.1e} <= {rtol:g})")

    def leg_a(self) -> list:
        from roc_tpu.analysis.hlo_audit import lower_steps
        from roc_tpu.train.driver import pallas_interpret

        self.say("Leg A: one device")
        cfg, trainer = self.build(self.base + self.kernels
                                  + ["-e", str(EPOCHS)])
        gd = trainer.gdata
        self.say(f"  aggregate_backend {cfg.aggregate_backend} -> "
                 f"{gd.backend}; {geometries(gd)}")
        self.check(gd.backend == "binned" and gd.plans is not None,
                   "resolved backend is binned (not xla, not matmul)")
        if self.rehearse:
            self.say(f"  pallas_interpret()={pallas_interpret()}: kernels "
                     f"run in the interpreter, no Mosaic call to look for")
        else:
            self.check(not pallas_interpret(), "pallas_interpret() is False")
            n = lower_steps(trainer)["train"].as_text().count(
                "tpu_custom_call")
            self.check(n > 0,
                       f"lowered train step holds {n} Mosaic custom call(s)")
        losses = self.train_and_check(trainer)
        del trainer, gd
        gc.collect()
        self.say(f"  parity: {PARITY_EPOCHS} epochs on -aggr-backend xla, "
                 f"same seed (one device: same dropout masks)")
        ref = self.first_losses(self.base + ["-aggr-backend", "xla"], "xla")
        self.check_close(losses, ref, KERNEL_VS_XLA_RTOL, ("binned", "xla"))
        return losses

    def leg_b(self):
        import jax
        from jax.sharding import NamedSharding

        from roc_tpu.analysis.hlo_audit import lower_steps
        from roc_tpu.parallel.mesh import PARTS_AXIS

        P_ = LEG_B_PARTS
        n_dev = len(jax.devices())
        if n_dev < P_:
            self.say(f"Leg B: not run: {n_dev} devices (needs {P_}; -parts "
                     f"{P_} here would overcommit, not distribute)")
            return None
        self.say(f"Leg B: {P_} devices")
        parted_argv = self.base + self.kernels + ["-parts", str(P_)]
        _, trainer = self.build(parted_argv + ["-e", str(EPOCHS)])
        self.check(one_part_per_device(trainer, P_),
                   f"mesh holds {P_} distinct devices, one part each "
                   f"(devices={len(set(trainer.mesh.devices.flat))}, "
                   f"k={trainer.k})")
        gd = trainer.gdata
        self.say(f"  exchange {trainer._exchange_mode}, backend "
                 f"{gd.backend}; {geometries(gd)}")
        self.check(trainer._exchange_mode == "halo" and gd.mode == "vertex"
                   and gd.backend == "binned",
                   "vertex sharding over the halo exchange, binned kernels")

        mesh_devs = set(trainer.mesh.devices.flat)
        operands = [trainer.x, trainer.labels, trainer.mask] + \
            jax.tree.leaves(gd)
        spread = all(
            isinstance(a.sharding, NamedSharding)
            and PARTS_AXIS in a.sharding.spec
            and len(a.addressable_shards) == P_
            and {s.device for s in a.addressable_shards} == mesh_devs
            and all(s.data.shape[0] * P_ == a.shape[0]
                    for s in a.addressable_shards)
            for a in operands)
        self.check(spread, f"all {len(operands)} node/graph operands "
                           f"sharded on '{PARTS_AXIS}', one shard per device")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in trainer.mesh.devices.flat]
        if self.rehearse and None in in_use:
            self.say("  this backend reports no memory_stats(): live-bytes "
                     "check not run")
        else:
            self.check(all(in_use),
                       "bytes_in_use non-zero on every device ("
                       + ", ".join(f"{b / 2**20:.0f} MiB" for b in in_use)
                       + ")")

        mark = self.compiles.mark()
        hlo = lower_steps(trainer)["train"].compile().as_text()
        n_a2a, n_ar = hlo.count("all-to-all"), hlo.count("all-reduce")
        self.say(f"  compiled train step: {self.compiles.since(mark)} "
                 f"[{self.stamp}]")
        self.check(n_a2a > 0 and n_ar > 0,
                   f"compiled step holds the halo all-to-all ({n_a2a} "
                   f"mention(s)) and the gradient all-reduce ({n_ar})")
        if not self.rehearse:
            self.check(hlo.count("tpu_custom_call") > 0,
                       "compiled step holds Mosaic custom calls")

        losses = self.train_and_check(trainer)
        del trainer, gd, operands
        gc.collect()

        self.say(f"  parity: {PARITY_EPOCHS} epochs with -dropout 0, {P_} "
                 f"parts vs one device (each device draws its own dropout "
                 f"mask, so only dropout-free losses compare)")
        single = self.first_losses(
            self.base + self.kernels + ["-dropout", "0"], "binned")
        parted = self.first_losses(parted_argv + ["-dropout", "0"], "binned")
        self.check_close(parted, single, SHARDED_VS_SINGLE_RTOL,
                         (f"{P_}-part", "single"))
        return losses


def main(argv) -> int:
    rehearse = argv == ["--rehearse-cpu"]
    if argv and not rehearse:
        print(__doc__, file=sys.stderr)
        return 2
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={LEG_B_PARTS}")

    import jax

    from roc_tpu import cache, device, native

    dev = device.describe()
    if not rehearse and not device.on_tpu():
        print(f"chip_smoke: JAX found no TPU ({device.banner()}); nothing "
              f"run.  `--rehearse-cpu` walks the script on the CPU.",
              file=sys.stderr)
        return 2
    run = Smoke(rehearse, device.banner())
    run.say(f"chip_smoke: {run.stamp}; jax {jax.__version__}; compile cache "
            f"at {cache.enable_compile_cache()}")
    summary = {"rehearsal": "cpu"} if rehearse else {}

    def finish(ok: bool, **detail) -> None:
        print(json.dumps({**summary, **detail, "claim": None}))
        print(json.dumps({"ok": ok, "device": dev}), flush=True)

    try:
        had_so = os.path.exists(native._SO)
        run.check(native.available(),
                  f"native plan builders loaded (libroc_native.so "
                  f"{'was already there' if had_so else 'built in this run'})")
        losses_a = run.leg_a()
        losses_b = run.leg_b()
    except Failed as e:
        finish(False, failed=str(e))
        return 1
    except Exception as e:      # a phase raised: say so, then the result
        traceback.print_exc()
        finish(False, failed=f"{type(e).__name__}: {e}"[:500])
        return 1
    finish(not rehearse,        # a rehearsal did not see the chip
           leg_a={"losses": losses_a},
           leg_b={"losses": losses_b} if losses_b is not None
           else f"not run: {dev['count']} devices",
           compiled=run.compiles.requests - run.compiles.hits,
           loaded_from_cache=run.compiles.hits)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
