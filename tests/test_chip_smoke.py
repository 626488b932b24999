"""``chip_smoke.py`` rehearsed on the CPU at the tiny config.

The chip run is the proof; this is its rehearsal (explicit arguments:
``--config tiny --platform cpu``), so that what can break without a chip —
paths, arguments, control flow, the shape of the lines the driver reads —
breaks here. Never by asking the sandbox for a TPU.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.e2e

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*argv, env=None, timeout=420):
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--config", "tiny",
         "--platform", "cpu", *argv],
        cwd=REPO,
        env={**os.environ, **(env or {})},
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    return proc, lines


def test_rehearsal_prints_a_line_per_phase_and_the_contract_line():
    proc, lines = _smoke()
    assert proc.returncode == 0, proc.stderr[-3000:]
    phases = {x["phase"]: x for x in lines[:-1]}
    assert list(phases) == ["train", "serve", "parent"]
    assert all(x["ok"] for x in lines)

    train = phases["train"]
    assert train["steps"] == 8 and len(train["losses"]) == 8
    assert train["losses"][-1] < train["losses"][0]
    assert train["dead_nodes"] == []
    # the feed went through the data plane as columnar frames
    assert sum(train["frames_by_path"].values()) == 8

    serve = phases["serve"]
    assert serve["checks"]["tokens_equal_generate"]  # float32: exact
    assert serve["engine"]["stopped_cleanly"] is True
    assert serve["max_logprob_diff"] <= serve["logit_tol"]

    # the parent orchestrates and never initialises a JAX backend
    assert phases["parent"]["checks"]["parent_off_jax"] is True
    # the last line is the contract's object and nothing else
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


def test_four_device_rehearsal_runs_only_the_sharded_path():
    proc, lines = _smoke("--chips", "4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert [x.get("phase") for x in lines[:-1]] == ["train", "parent"]
    train = lines[0]
    assert train["mesh"] == {"fsdp": 4}
    for tree in ("params", "mu", "nu"):
        placed = train["placement"][tree]
        assert placed["large_leaves"] > 0
        assert placed["spread_evenly"] == placed["large_leaves"]
    assert train["checks"]["losses_match_one_device"]
    assert sum(train["collectives"].values()) > 0
    assert lines[-1]["device"]["count"] == 4


def test_a_failed_phase_fails_the_run():
    """The node's feed producer is made to raise (the existing failpoint
    grammar, inherited by the node through the environment): the error
    ferry carries it to the parent, which exits non-zero and prints no
    result line."""
    proc, lines = _smoke(env={"TFOS_FAILPOINTS": "prefetch.producer=raise"})
    assert proc.returncode != 0
    assert not any(x.get("ok") is True and "device" in x for x in lines)
    assert "FailpointError" in proc.stderr


_HELPER = (
    "import jax;"
    "from tensorflowonspark_tpu.utils.util import enable_compile_cache;"
    "print(enable_compile_cache());"
    "print(jax.config.jax_compilation_cache_dir)"
)


@pytest.mark.parametrize("env_dir", [None, "/some/dir"], ids=["unset", "set"])
def test_compile_cache_helper_places_the_cache(env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory, and none set in
    code. Unset: ``<checkout>/.jax_cache``, identical in two processes."""
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    outs = [
        subprocess.run(
            [sys.executable, "-c", _HELPER], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split()
        # two processes, started from different directories
        for cwd in (REPO, os.path.join(REPO, "tests"))
    ]
    want = env_dir or os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]
