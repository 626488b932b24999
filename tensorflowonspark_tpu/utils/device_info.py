"""Accelerator discovery/allocation helpers.

Reference parity: ``tensorflowonspark/gpu_info.py`` (``get_gpus`` parsed
nvidia-smi, randomly picked free GPUs with retries, and emitted
``CUDA_VISIBLE_DEVICES``). On TPU there is no multi-tenant allocation race
to dodge: libtpu hands all of a host's chips to the ONE process that
loads it (a second process that asks fails within seconds on libtpu's
lock file), so a host runs one accelerator process; any other is
launched with ``utils.util.cpu_only_env()``. What remains useful is discovery and a
capability probe.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

MAX_RETRIES = 3  # kept for API parity; TPU allocation does not race


def get_gpus(num_gpu: int = 1, worker_index: int = -1) -> str:
    """Compatibility shim for reference callers: returns a CSV of local
    device ordinals (the string the reference put in CUDA_VISIBLE_DEVICES).

    Informational only: one process drives all of a TPU host's chips.
    """
    devices = get_local_devices()
    n = min(num_gpu, len(devices))
    return ",".join(str(i) for i in range(n))


def get_local_devices() -> list:
    import jax

    return jax.local_devices()


def is_gpu_available() -> bool:
    """Reference name; answers 'is an accelerator available'."""
    return is_tpu_available()


def is_tpu_available() -> bool:
    import jax

    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


_MULTIPROCESS_PROBE = """
import sys
import jax

jax.distributed.initialize(
    coordinator_address="127.0.0.1:%d", num_processes=2, process_id=%d
)
import numpy as np
from jax.experimental import multihost_utils

out = multihost_utils.process_allgather(np.ones((1,), np.int32))
assert int(np.asarray(out).sum()) == 2
"""

_multiprocess_supported: bool | None = None


def multiprocess_collectives_supported(timeout: float = 120.0) -> bool:
    """Can THIS host's backend actually run cross-process collectives?

    Some jaxlib builds reject multiprocess computations on the CPU
    backend ("Multiprocess computations aren't implemented on the CPU
    backend"), which makes every multi-controller e2e test fail for an
    environmental reason that is not a bug in this repo. This probe
    answers the question empirically — two short-lived CPU-only
    subprocesses join one ``jax.distributed`` coordinator and run a
    real allgather — and caches the verdict for the process lifetime.
    ``tests/test_distributed.py`` gates itself on it (``pytest.skip``
    instead of 7 pre-baselined failures). ``TFOS_MULTIPROCESS_OK=0/1``
    overrides the probe (CI images that already know their backend).
    """
    global _multiprocess_supported
    if _multiprocess_supported is not None:
        return _multiprocess_supported
    forced = os.environ.get("TFOS_MULTIPROCESS_OK")
    if forced is not None:
        _multiprocess_supported = forced not in ("0", "false", "")
        return _multiprocess_supported
    import subprocess
    import sys

    from tensorflowonspark_tpu.utils.util import cpu_only_env, find_free_port

    port = find_free_port()
    env = dict(os.environ, **cpu_only_env(num_cpu_devices=1))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MULTIPROCESS_PROBE % (port, pid)],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        for pid in (0, 1)
    ]
    ok = True
    deadline = None
    try:
        import time as _time

        deadline = _time.monotonic() + timeout
        for p in procs:
            remaining = max(0.1, deadline - _time.monotonic())
            try:
                ok = p.wait(timeout=remaining) == 0 and ok
            except subprocess.TimeoutExpired:
                ok = False
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    _multiprocess_supported = ok
    logger.info(
        "multiprocess collectives %s on this backend",
        "supported" if ok else "NOT supported",
    )
    return ok
