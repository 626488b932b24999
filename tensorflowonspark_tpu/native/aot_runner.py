"""Build/run helper for the no-Python SavedModel runner (aot_runner.cc).

The runner binary itself never touches Python — this module only
discovers the TensorFlow pip package's headers/libraries, compiles the
binary on demand (cached in ``native/build/`` under a name keyed on the
source, like the native library), and offers a subprocess convenience
wrapper for tests and tooling.
"""

from __future__ import annotations

import importlib.util
import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCE = os.path.join(_DIR, "aot_runner.cc")
_BIN_NAME = "aot_runner"

_lock = threading.Lock()
_bin: str | None = None
_build_failed = False


def _tf_base() -> str | None:
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.origin:
        return None
    return os.path.dirname(spec.origin)


def build_runner() -> str | None:
    """Compile (unless this very source was built already) and return the
    runner binary path; None when TensorFlow or the C++ toolchain is
    unavailable."""
    global _bin, _build_failed
    if _bin is not None or _build_failed:
        return _bin
    with _lock:
        if _bin is not None or _build_failed:
            return _bin
        base = _tf_base()
        if base is None:
            logger.warning("tensorflow not installed; aot_runner unavailable")
            _build_failed = True
            return None
        build_dir = os.environ.get("TFOS_NATIVE_BUILD_DIR") or os.path.join(
            _DIR, "build"
        )
        os.makedirs(build_dir, exist_ok=True)
        cmd = [
            os.environ.get("CXX", "g++"),
            "-O2",
            "-std=c++17",
            "-Wall",
            _SOURCE,
            f"-I{os.path.join(base, 'include')}",
            f"-L{base}",
            "-l:libtensorflow_cc.so.2",
            "-l:libtensorflow_framework.so.2",
            f"-Wl,-rpath,{base}",
        ]
        from tensorflowonspark_tpu.native import build_key

        bin_path = os.path.join(
            build_dir, f"{_BIN_NAME}-{build_key([_SOURCE], cmd)}"
        )
        if not os.path.exists(bin_path):
            tmp = bin_path + f".tmp.{os.getpid()}"  # atomic vs concurrent builders
            cmd += ["-o", tmp]
            logger.info("building aot_runner: %s", " ".join(cmd))
            try:
                subprocess.run(cmd, check=True, capture_output=True, text=True)
                os.replace(tmp, bin_path)
            except (OSError, subprocess.CalledProcessError) as e:
                detail = getattr(e, "stderr", "") or str(e)
                logger.warning(
                    "aot_runner build failed: %s", detail.strip()[:800]
                )
                _build_failed = True
                return None
        _bin = bin_path
    return _bin


def run_saved_model(saved_model_dir: str, inputs, out_dir: str) -> dict:
    """Run the C++ binary over ``inputs`` (list of np arrays, manifest
    order) and return {logical_name: np.ndarray} outputs.

    Every inference step happens in the subprocess — this wrapper only
    stages .npy files, so it doubles as the CI proof that the artifact
    is consumable without a Python interpreter."""
    import numpy as np

    binary = build_runner()
    if binary is None:
        raise RuntimeError("aot_runner binary unavailable (no TF or no g++)")
    os.makedirs(out_dir, exist_ok=True)
    args = [binary, saved_model_dir]
    for i, arr in enumerate(inputs):
        path = os.path.join(out_dir, f"in{i}.npy")
        np.save(path, np.ascontiguousarray(arr))
        args += ["--in", path]
    prefix = os.path.join(out_dir, "out_")
    args += ["--out-prefix", prefix]
    proc = subprocess.run(args, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"aot_runner failed (rc={proc.returncode}): {proc.stderr[-800:]}"
        )
    out = {}
    for line in proc.stdout.splitlines():
        logical = line.split(" ", 1)[0]
        path = f"{prefix}{logical}.npy"
        if os.path.exists(path):
            out[logical] = np.load(path)
    return out
