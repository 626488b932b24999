"""Small host-side utilities.

Reference parity: ``tensorflowonspark/util.py`` (get_ip_address,
find_in_path, write_executor_id/read_executor_id, single_node_env).
"""

from __future__ import annotations

import errno
import os
import socket


EXECUTOR_ID_FILE = "executor_id"


def resolve_path(path: str, default_fs: str = "", working_dir: str = "") -> str:
    """Resolve a user path against a default FS / working dir.

    Reference: ``TFNode.py:hdfs_path`` resolution matrix — scheme-qualified
    paths pass through; absolute paths go under default_fs (when it is a
    scheme URI); relative paths resolve against the working dir (cwd when
    unset). Shared by ``TFNodeContext.absolute_path`` and the node
    runtime's tensorboard/log-dir handling so they always agree.
    """
    if "://" in path:  # fully qualified (hdfs://, gs://, file://, ...)
        return path
    if path.startswith("/"):
        fs = default_fs.rstrip("/")
        return f"{fs}{path}" if fs and "://" in default_fs else path
    base = (working_dir or os.getcwd()).rstrip("/")
    return f"{base}/{path}"


def get_ip_address() -> str:
    """Best-effort externally-routable IP of this host.

    Uses the UDP-connect trick (no packets are actually sent): connect a
    datagram socket to a public address and read the local endpoint the
    kernel chose. Falls back to loopback in fully isolated environments.
    Reference: ``util.py:get_ip_address``.
    """
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 53))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def find_in_path(path: str, file_name: str) -> str | None:
    """Find ``file_name`` in the ``os.pathsep``-separated ``path`` string.

    Reference: ``util.py:find_in_path`` (used to locate the tensorboard
    binary on executors).
    """
    for p in path.split(os.pathsep):
        candidate = os.path.join(p, file_name)
        if os.path.exists(candidate) and os.path.isfile(candidate):
            return candidate
    return None


def write_executor_id(num: int, cwd: str | None = None) -> None:
    """Pin this executor's logical id to a file in its working dir.

    Task retries land in the same working directory, so a retried feed task
    rediscovers which logical node it belongs to instead of grabbing a fresh
    partition id. Reference: ``util.py:write_executor_id``.
    """
    path = os.path.join(cwd or os.getcwd(), EXECUTOR_ID_FILE)
    with open(path, "w") as f:
        f.write(str(num))


def read_executor_id(cwd: str | None = None) -> int | None:
    """Read the pinned executor id, or None if this is the first task here.

    Reference: ``util.py:read_executor_id``.
    """
    path = os.path.join(cwd or os.getcwd(), EXECUTOR_ID_FILE)
    try:
        with open(path) as f:
            return int(f.read())
    except (OSError, ValueError):
        return None


def cpu_only_env(num_cpu_devices: int | None = None) -> dict[str, str]:
    """Env vars that force a subprocess to boot pure-CPU JAX.

    A chip belongs to one process at a time, so every process that is not
    meant to drive it (extra nodes on a TPU host, feed-only nodes, test
    children) is launched with this: ``JAX_PLATFORMS=cpu`` keeps libtpu
    from being loaded at all.
    """
    env = {"JAX_PLATFORMS": "cpu"}
    if num_cpu_devices is not None:
        env["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={num_cpu_devices}"
        ).strip()
    return env


def compile_cache_dir() -> str:
    """Where this process keeps JAX's persistent compilation cache:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
    ``<checkout>/.jax_cache`` — a fixed path derived from the package's
    location, so every process of a checkout (driver, nodes, servers,
    benches, one call after another) shares one cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
        ".jax_cache",
    )


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call once at start-up,
    before the first compile, in every entry point that compiles. With
    ``JAX_COMPILATION_CACHE_DIR`` set JAX has already read it and no
    directory is set in code. Returns the directory in use.
    ``JAX_ENABLE_COMPILATION_CACHE=false`` (JAX's own switch) still turns
    the cache off whatever the directory."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def single_node_env(num_cpu_devices: int | None = None) -> None:
    """Configure env vars for a single-process, host-only JAX run.

    Used by inference/transform workers and tests that must not grab the TPU.
    Reference: ``util.py:single_node_env`` (which hid GPUs and capped
    threads for single-node TF).
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if num_cpu_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        opt = f"--xla_force_host_platform_device_count={num_cpu_devices}"
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + opt).strip()


def find_free_port(host: str = "") -> int:
    """Reserve an OS-assigned free TCP port and release it immediately.

    Mirrors the reference's reserve-then-release port dance
    (``TFSparkNode.py:_mapfn``: bind on port 0, hand the port to the
    reservation, close the socket just before the engine binds it). There is
    an inherent race window; callers must tolerate rebinding.
    """
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        s.bind((host, 0))
        return s.getsockname()[1]
    finally:
        s.close()


def ensure_dir(path: str) -> str:
    """mkdir -p that tolerates concurrent creation across hosts."""
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:  # pragma: no cover - exotic FS races
        if e.errno != errno.EEXIST:
            raise
    return path
