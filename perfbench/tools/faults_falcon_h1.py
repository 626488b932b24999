#!/usr/bin/env python3
"""Plant one fault underneath the timed path of a Falcon-H1 serve cell
and run the cell as ``perfbench/run.py`` does: ``correct`` has to come out
false.

    python3 perfbench/tools/faults_falcon_h1.py --fault <name> \
        --workload <cell> --seed <n> --seconds <s> [--rehearse]

- ``ssm_zeroed``: the admitted row's ``cache/ssm`` is zeroed at
  admission (what the prefill program hands to the admit program), as if
  the recurrent state were not scattered into the batch;
- ``valid_ignored``: the scan and the convolution take no validity mask,
  so a prompt's padding runs through the recurrence;
- ``key_multiplier_dropped``: the model is built with ``key_multiplier``
  1, as if ``Attention`` did not apply it.

The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@contextlib.contextmanager
def ssm_zeroed():
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.serving.engine import ContinuousBatcher as CB

    orig = CB._prefill_fn

    def broken(self, width):
        prefill = orig(self, width)

        def zeroing(*args):
            cache, *rest = prefill(*args)
            cache = jax.tree_util.tree_map_with_path(
                lambda p, x: jnp.zeros_like(x) if p[-1].key == "ssm" else x, cache)
            return (cache, *rest)

        return zeroing

    CB._prefill_fn = broken
    try:
        yield
    finally:
        CB._prefill_fn = orig


@contextlib.contextmanager
def valid_ignored():
    from tensorflowonspark_tpu.models import falcon_h1

    scan, conv = falcon_h1.ssd_scan, falcon_h1.causal_conv1d
    falcon_h1.ssd_scan = lambda *a, valid=None, **kw: scan(*a, **kw)
    falcon_h1.causal_conv1d = lambda xBC, w, b, window=None, valid=None: conv(xBC, w, b, window)
    try:
        yield
    finally:
        falcon_h1.ssd_scan, falcon_h1.causal_conv1d = scan, conv


@contextlib.contextmanager
def key_multiplier_dropped():
    from tensorflowonspark_tpu.models import falcon_h1

    orig = falcon_h1.from_hf_config
    falcon_h1.from_hf_config = lambda hf, **over: orig(hf, **{**over, "key_multiplier": 1.0})
    try:
        yield
    finally:
        falcon_h1.from_hf_config = orig


FAULTS = {f.__name__: f for f in (ssm_zeroed, valid_ignored, key_multiplier_dropped)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    a, rest = ap.parse_known_args()
    from perfbench import run

    with FAULTS[a.fault]():
        return run.main(rest)


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
