"""CPU tests of the chip smoke's plumbing: what must hold before a chip
minute is spent.  (The legs themselves need the chip; `python
chip_smoke.py --rehearse-cpu` walks them at a tiny size.)"""

import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from roc_tpu import cache
from roc_tpu.obs import roofline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compile_cache_env_set_sets_nothing(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself; the helper must
    not touch jax.config."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert cache.enable_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        a = cache.enable_compile_cache()
        b = cache.enable_compile_cache()
        assert a == b == os.path.join(ROOT, ".cache", "jax")
        assert jax.config.jax_compilation_cache_dir == a
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_derived_artefacts_default_into_checkout(monkeypatch):
    """Plans and the tuned store sit under <checkout>/.cache unless an
    environment variable puts them elsewhere."""
    from roc_tpu.ops.pallas import binned
    from roc_tpu.tune import store
    for var in ("ROC_PLAN_CACHE", "ROC_PLAN_CACHE_DIR", "ROC_TUNED_PATH",
                "ROC_NO_TUNED"):
        monkeypatch.delenv(var, raising=False)
    plans = os.path.join(ROOT, ".cache", "plans")
    assert binned._plan_cache_dir() == plans
    assert store.tuned_store_path() == os.path.join(plans, "tuned.json")


def test_no_chip_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 2
    assert r.stdout == ""
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("outcome", ["pass", "check", "raise"])
def test_last_line_is_the_result_object(outcome, monkeypatch, capsys):
    """Whatever the legs did, standard output ends with exactly
    {"ok", "device": {"platform", "kind", "count"}}; the summary, ending
    "claim": null, is the line before it."""
    import json
    smoke = _chip_smoke()
    for var in ("JAX_PLATFORMS", "XLA_FLAGS"):      # main() sets both
        monkeypatch.setenv(var, os.environ.get(var, ""))

    def leg_a(self):
        if outcome == "check":
            self.check(False, "a check that does not hold")
        if outcome == "raise":
            raise RuntimeError("a phase that raised")
        return [2.0, 1.0]

    monkeypatch.setattr(smoke.Smoke, "leg_a", leg_a)
    monkeypatch.setattr(smoke.Smoke, "leg_b", lambda self: None)
    rc = smoke.main(["--rehearse-cpu"])
    assert rc == (0 if outcome == "pass" else 1)
    lines = capsys.readouterr().out.strip().splitlines()
    result, summary = json.loads(lines[-1]), json.loads(lines[-2])
    assert set(result) == {"ok", "device"} and result["ok"] is False
    dev = jax.devices()
    assert result["device"] == {"platform": dev[0].platform,
                                "kind": dev[0].device_kind,
                                "count": len(dev)}
    assert lines[-2].endswith('"claim": null}')
    assert ("failed" in summary) == (outcome != "pass")


def test_parts_on_fewer_devices_is_refused(monkeypatch):
    """`-parts 4` on two devices overcommits (k=2) without failing; the
    smoke's predicate must tell that apart from four parts on four."""
    from roc_tpu.graph import datasets
    from roc_tpu.models import build_gcn
    from roc_tpu.parallel.spmd import SpmdTrainer
    from roc_tpu.train.config import Config

    smoke = _chip_smoke()
    ds = datasets.synthetic("t", 200, 3.0, 12, 4, n_train=50, n_val=50,
                            n_test=50, seed=31)
    cfg = Config(layers=[12, 8, 4], num_parts=4, eval_every=10**9)
    four = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    assert smoke.one_part_per_device(four, 4)
    devs = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a: devs[:2])
    two = SpmdTrainer(cfg, ds, build_gcn(cfg.layers, 0.0))
    assert two.k == 2
    assert not smoke.one_part_per_device(two, 4)


def test_unknown_device_kind_has_no_roofline():
    assert roofline.peaks_for("TPU v5 lite").flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.mfu(1e12, 1.0, 1, "TPU v99")
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.roofline_frac(1e12, 1e9, 1.0, 1, "cpu")


def test_surrogate_tuned_entry_ignored_on_tpu(tmp_path, monkeypatch):
    """A winner the CPU surrogate picked must not decide which kernel the
    chip compiles; a device-measured one still does."""
    from roc_tpu.ops.pallas.binned import GEOM_MID
    from roc_tpu.tune import store

    rng = np.random.default_rng(0)
    src, dst = rng.integers(0, 600, 4000), rng.integers(0, 600, 4000)
    path = str(tmp_path / "tuned.json")

    def write(source):
        entry = {"geom": list(GEOM_MID), "knobs": {}, "modeled_s": 1.0,
                 "trial_s": 1.0, "source": source}
        store.merge_entries(
            path, {store.graph_key(src, dst, 600, 600): {"fp32": entry}},
            interpret=(source == "surrogate"), seed=0)

    write("surrogate")
    assert store.lookup(src, dst, 600, 600, path=path)[0] == GEOM_MID
    monkeypatch.setattr(store, "on_tpu", lambda: True)
    assert store.lookup(src, dst, 600, 600, path=path) == (None, None)
    assert store.stale_plan_geom(src, dst, 600, 600,
                                 GEOM_MID._replace(slot=64),
                                 path=path) is None
    write("device")
    assert store.lookup(src, dst, 600, 600, path=path)[0] == GEOM_MID


# Smallest vmem_limit_bytes (MiB, whole numbers) at which Mosaic compiled
# each two-pass kernel for a v5e, found by bisection with libtpu's compiler
# in the sandbox (jax 0.9.0, libtpu 0.0.34; plan arrays at the Reddit
# scale: C1=512, C2=256): (preset, H, exact, phase 1, phase 2).  Bisected
# again by PR 26 for the kernels that read lane-dense index rows: phase 1
# asks for more where it is `exact` (three contractions on dimension 0,
# each with its turned one-hot), phase 2 for less everywhere (no (CH2, 1)
# block lane-padded to 2 MB, no transposed left operand).
_MIN_VMEM_MIB = [
    ("default", 128, 0, 3, 5), ("default", 128, 1, 9, 12),
    ("default", 256, 0, 9, 11), ("default", 256, 1, 16, 23),
    ("default", 512, 0, 13, 18), ("default", 512, 1, 30, 39),
    ("flat", 128, 0, 6, 7), ("flat", 128, 1, 31, 12),
    ("flat", 256, 0, 19, 15), ("flat", 256, 1, 53, 23),
    ("flat", 512, 0, 29, 24), ("flat", 512, 1, 97, 39),
    ("wide", 256, 0, 18, 21), ("wide", 256, 1, 31, 43),
    ("sparse", 256, 0, 13, 9), ("sparse", 256, 1, 21, 15),
    ("xsparse", 256, 0, 12, 10), ("xsparse", 256, 1, 18, 14),
    ("flat_sparse", 256, 0, 21, 11), ("flat_sparse", 256, 1, 39, 15),
    ("flat_bf16", 128, 0, 4, 5), ("flat_bf16", 512, 0, 37, 18),
]


def test_vmem_model_bounds_what_mosaic_needed():
    """The scoped-VMEM request the kernels pass to Mosaic must cover what
    the compiler was measured to need, and every geometry the policy may
    pick must be admitted at the nominal width."""
    from roc_tpu.ops.pallas import binned as B
    presets = dict(B.GEOM_PRESETS, default=B._default_geom())
    for name, H, exact, p1, p2 in _MIN_VMEM_MIB:
        g = presets[name]
        assert B._p1_vmem_bytes(g, H, bool(exact)) >= p1 << 20, (name, H)
        assert B._p2_vmem_bytes(g, H, bool(exact)) >= p2 << 20, (name, H)
        assert B._vmem_bytes(g, H, bool(exact)) <= B._VMEM_LIMIT_MAX
    for g in presets.values():
        assert B._vmem_bytes(g) <= B._VMEM_NOMINAL_CAP, g
