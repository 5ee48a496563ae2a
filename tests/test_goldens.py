"""Golden-trajectory regression: the serial path must replay the captured
goldens *bitwise* (tests/goldens/*.npz, captured by
scripts/capture_goldens.py).

This is the engine's strongest no-regression net: it catches any change to
the serial protocol order, RNG stream, or numerics — including ones that
would silently pass allclose-level tests.  On failure the mismatching
arrays are dumped to ``$GOLDEN_DIFF_DIR`` (default: a fresh temporary
directory) so CI can upload them as artifacts for offline inspection.

Each golden records the ``jax.__version__`` that captured it; a replay under
another version fails up front, naming both, since XLA numerics and the
default PRNG stream move between releases.  If a trajectory change is
*intentional*, or jax was upgraded, regenerate with

    PYTHONPATH=src python scripts/capture_goldens.py
"""
import glob
import importlib.util
import os
import tempfile

import jax
import numpy as np
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN_DIR = os.path.join(_HERE, "goldens")


def _load_capture_module():
    path = os.path.join(os.path.dirname(_HERE), "scripts",
                        "capture_goldens.py")
    spec = importlib.util.spec_from_file_location("capture_goldens", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


capture = _load_capture_module()
GOLDEN_NAMES = sorted(
    os.path.splitext(os.path.basename(p))[0]
    for p in glob.glob(os.path.join(GOLDEN_DIR, "*.npz")))


def test_goldens_cover_every_config():
    """Every config in the capture grid has a checked-in golden (a new
    registry rule or gating mode without a captured trajectory fails here
    until `scripts/capture_goldens.py` is re-run)."""
    assert GOLDEN_NAMES, f"no goldens found in {GOLDEN_DIR}"
    missing = set(capture.golden_configs()) - set(GOLDEN_NAMES)
    assert not missing, f"goldens not captured for: {sorted(missing)}"


@pytest.mark.parametrize("name", GOLDEN_NAMES)
def test_golden_trajectory_bitwise(name):
    configs = capture.golden_configs()
    assert name in configs, (
        f"stale golden {name}.npz: config no longer in the capture grid")
    got = capture.run_config(configs[name])
    want = np.load(os.path.join(GOLDEN_DIR, f"{name}.npz"))
    assert "jax_version" in want.files, (
        f"{name}.npz records no jax version: re-run scripts/capture_goldens.py")
    captured = str(want["jax_version"])
    assert captured == jax.__version__, (
        f"golden {name} was captured under jax {captured} but jax "
        f"{jax.__version__} is installed: re-run scripts/capture_goldens.py")

    mismatches = {}
    for key in want.files:
        if key == "jax_version":
            continue
        g = np.asarray(got[key])
        w = want[key]
        if g.shape != w.shape or not np.array_equal(g, w):
            mismatches[key] = (w, g)
    extra = set(map(str, got)) - set(want.files)
    assert not extra, f"{name}: arrays missing from golden: {sorted(extra)}"

    if mismatches:
        diff_dir = (os.environ.get("GOLDEN_DIFF_DIR")
                    or tempfile.mkdtemp(prefix="golden_diffs_"))
        os.makedirs(diff_dir, exist_ok=True)
        dump = {}
        for key, (w, g) in mismatches.items():
            dump[f"want_{key}"] = w
            dump[f"got_{key}"] = np.asarray(g)
        diff_path = os.path.join(diff_dir, f"{name}.npz")
        np.savez_compressed(diff_path, **dump)
        detail = {
            k: (f"max|Δ|={np.max(np.abs(w.astype(np.float64) - np.asarray(g, np.float64))):.3e}"
                if w.shape == np.shape(g) else
                f"shape {w.shape} vs {np.shape(g)}")
            for k, (w, g) in mismatches.items()
        }
        pytest.fail(
            f"golden {name} mismatch (diff dumped to {diff_path}): {detail}")


def test_goldens_are_jax_default_prng():
    """The goldens assume the default threefry PRNG; a config flip would
    invalidate every file at once with a confusing bitwise diff."""
    assert jax.config.jax_default_prng_impl == "threefry2x32"
