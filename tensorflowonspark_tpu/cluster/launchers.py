"""Launchers: who plays Spark's role of getting node processes running.

The reference leaned on Spark's scheduler (``sc.parallelize(...)
.foreachPartition(TFSparkNode.run)`` — one long-lived task per executor,
SURVEY.md §3.1). With no Spark in the picture, a launcher owns that step:

- :class:`LocalLauncher` — N processes on this host (the test/CI analog of
  the reference's local-mode Spark trick, and the single-TPU-VM path).
- :class:`HostListLauncher` — one process per remote host via a command
  template (ssh by default); the multi-host TPU-pod path where each TPU-VM
  host runs one node process that owns its local chips.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import os
import shlex
import subprocess
import sys
import time
from typing import Callable, Sequence

logger = logging.getLogger(__name__)


class LocalLauncher:
    """Spawn node processes on the local host.

    Uses the ``spawn`` start method: node processes initialize their own
    JAX runtime, and forking a process that may already hold TPU/XLA
    runtime threads is unsafe.
    """

    def __init__(self, env: dict[str, str] | None = None):
        self.env = env or {}
        self._procs: list[mp.Process] = []

    def launch(
        self,
        num_nodes: int,
        target: Callable[..., None],
        args_for: Callable[[int], tuple],
        env: dict[str, str] | None = None,
    ) -> None:
        merged = {**self.env, **(env or {})}
        # What the nodes will ask JAX for: their JAX_PLATFORMS, else
        # what jax defaults to on this host — "tpu,cpu" where it sees
        # TPU chips, read here from this process's import of it.
        import jax

        platforms = (
            merged.get("JAX_PLATFORMS")
            or os.environ.get("JAX_PLATFORMS")
            or jax.config.jax_platforms
            or ""
        )
        if num_nodes > 1 and "tpu" in platforms.split(","):
            # A host's chips belong to ONE process at a time: every
            # further node would fail in libtpu ("multi-process
            # lockfile") at its first jax call. Say so before any starts.
            raise ValueError(
                f"{num_nodes} node processes on this host would all ask "
                f"for its TPU (JAX platforms {platforms!r}); run ONE node "
                "process per host — it drives all of the host's chips — "
                "or launch CPU-only nodes with env=cpu_only_env()"
            )
        ctx = mp.get_context("spawn")
        # Env vars must be in place BEFORE the child interpreter boots
        # (jax snapshots JAX_PLATFORMS/XLA_FLAGS at import). Spawn
        # inherits the parent's environ at exec, so set/restore here.
        saved = {k: os.environ.get(k) for k in merged}
        os.environ.update(merged)
        try:
            for i in range(num_nodes):
                proc = ctx.Process(
                    target=_child_main,
                    args=(merged, target, args_for(i)),
                    name=f"tfos-node-{i}",
                    daemon=False,
                )
                proc.start()
                self._procs.append(proc)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def poll_failed(self) -> list[int]:
        """Indices of processes that already exited nonzero."""
        return [
            i
            for i, p in enumerate(self._procs)
            if p.exitcode is not None and p.exitcode != 0
        ]

    def wait(self, timeout: float | None = None) -> bool:
        """Join all processes; True if all exited within the timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        for p in self._procs:
            remaining = (
                None if deadline is None else max(0.0, deadline - time.monotonic())
            )
            p.join(remaining)
        return all(p.exitcode is not None for p in self._procs)

    def exitcodes(self) -> list[int | None]:
        return [p.exitcode for p in self._procs]

    def terminate(self) -> None:
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        for p in self._procs:
            p.join(5)
        for p in self._procs:
            if p.is_alive():  # pragma: no cover - last resort
                p.kill()


def _child_main(env: dict[str, str], target, args) -> None:
    os.environ.update(env)
    target(*args)


class HostListLauncher:
    """Launch one node process per remote host via a command template.

    Runs ``python -m tensorflowonspark_tpu.cluster.node_main --payload ...``
    on each host through ``cmd_template`` (plain ssh by default; reference
    ``{command}`` unquoted — it is substituted pre-quoted as one shell
    word, see :meth:`launch_command`). This is the spark-submit-shaped
    path for real pods; the user ``map_fun``'s module must be importable
    on every host (the contract Spark imposed on the reference's
    ``map_fun`` too).
    """

    def __init__(
        self,
        hosts: Sequence[str],
        cmd_template: str = "ssh {host} {command}",
        python: str | None = None,
        env: dict[str, str] | None = None,
    ):
        self.hosts = list(hosts)
        self.cmd_template = cmd_template
        # sys.executable, not bare "python": PATH on the remote side may
        # name a different interpreter (or none) — callers with genuinely
        # heterogeneous hosts can still pass python="python3" etc.
        self.python = python or sys.executable
        self.env = dict(env or {})
        self._procs: list[subprocess.Popen] = []

    def launch(
        self,
        num_nodes: int,
        target: Callable[..., None],
        args_for: Callable[[int], tuple],
        env: dict[str, str] | None = None,
    ) -> None:
        from tensorflowonspark_tpu.cluster.node_main import encode_payload

        if num_nodes != len(self.hosts):
            raise ValueError(
                f"{num_nodes} nodes requested but {len(self.hosts)} hosts "
                "configured"
            )
        # Env must be on the remote command line (a local os.environ set
        # would not cross the ssh boundary).
        merged = {**self.env, **(env or {})}
        env_prefix = ""
        if merged:
            assignments = " ".join(
                f"{k}={shlex.quote(v)}" for k, v in merged.items()
            )
            env_prefix = f"env {assignments} "
        commands = []
        for i in range(num_nodes):
            payload = encode_payload(*args_for(i))
            commands.append(
                f"{env_prefix}{self.python} "
                f"-m tensorflowonspark_tpu.cluster.node_main "
                f"--payload {payload}"
            )
        self.launch_command(commands)

    def launch_command(self, commands: Sequence[str]) -> None:
        """Run one command per host through the template.

        ``{command}`` is substituted pre-quoted as ONE shell word, and the
        full line runs through the local shell — so every template sees
        exactly two shell parses: local (strips the quoting; the command
        reaches ssh/sh as a single argument) and remote/inner (parses the
        command itself, where per-value ``shlex.quote``s apply). This is
        what lets env values with spaces survive an ssh hop.
        """
        assert len(commands) == len(self.hosts)
        for host, command in zip(self.hosts, commands):
            full = self.cmd_template.format(
                host=shlex.quote(host), command=shlex.quote(command)
            )
            logger.info("launching on %s: %s", host, full)
            self._procs.append(subprocess.Popen(full, shell=True))

    def wait(self, timeout: float | None = None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        for p in self._procs:
            try:
                remaining = (
                    None
                    if deadline is None
                    else max(0.0, deadline - time.monotonic())
                )
                p.wait(remaining)
            except subprocess.TimeoutExpired:
                return False
        return True

    def poll_failed(self) -> list[int]:
        return [
            i
            for i, p in enumerate(self._procs)
            if p.poll() is not None and p.returncode != 0
        ]

    def exitcodes(self) -> list[int | None]:
        return [p.poll() for p in self._procs]

    def terminate(self) -> None:
        for p in self._procs:
            if p.poll() is None:
                p.terminate()


def default_launcher(num_nodes: int) -> LocalLauncher:
    return LocalLauncher()
