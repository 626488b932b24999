"""The package's box diagram, as a table that this test holds.

One case per subpackage or top-level module of ``tensorflowonspark_tpu``:
every import in its files is read with ``ast`` and must stay inside
``ALLOWED`` (which unit may import which), or be one of the ``UPWARD``
edges, the imports that point against the layering today. Each of those
is listed by file and module with what it is there for, so a new one
fails here, and one that has been repaired fails too until its line is
struck (ROADMAP D17). ``docs/DESIGN.md`` §1 describes the same layers in
prose.
"""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_NAME = "tensorflowonspark_tpu"
PKG = os.path.join(ROOT, PKG_NAME)

# unit -> the units its files may import. Lowest layers first.
ALLOWED = {
    # leaves: import nothing of the package
    "native": set(),
    "obs": set(),
    "streaming": set(),
    "utils": {"obs"},
    # the math: kernels, then mesh-aware wrappers, then the step, then models
    "ops": {"utils"},
    "parallel": {"ops", "utils"},
    "compute": {"parallel", "obs", "utils"},
    "models": {"compute", "ops", "parallel"},
    # records in
    "data": {"native", "utils"},
    "feed": {"compute", "data", "native", "obs", "utils"},
    # tokens out
    "cachetier": {"obs", "utils"},
    "serving": {
        "cachetier", "compute", "models", "obs", "ops", "parallel", "utils",
    },
    "autotune": {"obs", "utils"},
    # the cluster, and what drives it
    "cluster": {"compute", "feed", "native", "obs", "streaming", "utils"},
    "online": {"cluster", "feed", "obs", "serving", "utils"},
    "api": {"cluster", "compute", "data", "feed"},
    "tfnode": {"feed"},
    "launcher": {"cluster"},
    # entry points and static analysis sit on top
    "tools": {
        "api", "cachetier", "cluster", "compute", "data", "models", "obs",
        "ops", "serving", "utils",
    },
    "analysis": {"compute"},
}

# Where a unit is allowed, but only these modules of it.
ONLY_MODULES = {
    # PERF.md §4: the engine imports no model; it knows the cache's leaves
    ("serving", "models"): {"models.decode_cache"},
}

# The numeric layers never reach up into the systems around them.
NUMERIC = {"ops", "parallel", "models", "native"}
SYSTEMS = {"serving", "cluster", "api", "tools", "online", "cachetier"}

# Nothing in the package imports what is built on top of it.
OUTSIDE = {"perfbench", "benchmarks", "tests", "examples"}

# (file, imported module) -> why it points upward today.
UPWARD = {
    # the wire-schema registry lives in cluster/, and every plane that
    # speaks across a process boundary declares its payloads there
    ("feed/columnar.py", "cluster.wire"): "columnar.frame_header",
    ("feed/datafeed.py", "cluster.wire"): "cursor entries, kv.* keys",
    ("feed/ingest.py", "cluster.wire"): "ingest.cursor_payload, cursor entries",
    ("feed/livelog.py", "cluster.wire"): "livelog.manifest",
    ("cachetier/service.py", "cluster.wire"): "cachetier.* requests and replies",
    ("serving/rollout.py", "cluster.wire"): "rollout.manifest, rollout.latest",
    ("compute/elastic.py", "cluster.wire"): "ELASTIC_STATE_KEY",
    # markers are the push plane's in-band control records
    ("feed/datafeed.py", "cluster.marker"): "EndOfFeed / EndPartition",
    # the elastic trainer dials the node's manager for the roster epoch
    ("compute/elastic.py", "cluster.node"): "connect_manager",
    # TFCluster.run_online returns the loop it starts
    ("cluster/tfcluster.py", "online"): "OnlineLoop",
    # kernels that route themselves through shard_map under an ambient mesh
    ("ops/attention.py", "parallel"): "current_mesh, ring and ulysses re-entry",
    ("ops/attention.py", "parallel.context"): "dispatch_mesh, sp_specs_and_args",
    ("ops/attention.py", "compute.layout"): "activation specs of the table",
    ("ops/bn_kernels.py", "parallel.context"): "dispatch_mesh",
    ("ops/decode_attention.py", "parallel.context"): "current_mesh",
    ("ops/kda.py", "parallel.context"): "current_mesh",
    ("ops/selective_scan.py", "parallel.context"): "current_mesh",
    ("ops/bn_kernels.py", "compute.layout"): "activation specs of the table",
    ("parallel/moe.py", "compute.layout"): "expert specs of the table",
    # readers hand their records over in the feed's frame format
    ("data/readers.py", "feed.columnar"): "columnize_records",
    ("data/grain_source.py", "feed.columnar"): "scan_frames, decode_frame",
    # the lock witness reuses the static analyzer's guard collector
    ("utils/lockwitness.py", "analysis.core"): "Module, _comment_map",
    ("utils/lockwitness.py", "analysis.locks"): "LOCKFREE_RE, _GuardCollector",
    # the reference's compat.py shim: export_saved_model saves a checkpoint
    ("utils/compat.py", "compute.checkpoint"): "save_checkpoint",
}


def _unit_files() -> dict[str, list[str]]:
    """{unit: its .py files, relative to the package}; ``__init__.py`` of
    the package itself is the public face and belongs to no unit."""
    units: dict[str, list[str]] = {}
    for entry in sorted(os.listdir(PKG)):
        path = os.path.join(PKG, entry)
        if entry.endswith(".py") and entry != "__init__.py":
            units[entry[:-3]] = [entry]
        elif os.path.isfile(os.path.join(path, "__init__.py")):
            units[entry] = sorted(
                os.path.relpath(os.path.join(dirpath, f), PKG)
                for dirpath, _dirs, files in os.walk(path)
                for f in files
                if f.endswith(".py")
            )
    return units


UNITS = _unit_files()


def _unit_of(rel: str) -> str:
    """The unit a file of the package belongs to (``online.py`` is the
    unit ``online``)."""
    return rel.split("/")[0].removesuffix(".py")


def _is_module(dotted: str) -> bool:
    path = os.path.join(PKG, *dotted.split("."))
    return os.path.isfile(path + ".py") or os.path.isdir(path)


def _imports(rel: str) -> tuple[set[str], set[str]]:
    """(modules of the package, top-level names outside it) that one
    file imports, at any depth of the file: lazy imports count. A module
    of the package is written without the package's name and cut to two
    parts (``cluster.wire``); ``from pkg.cluster import wire`` names the
    module ``cluster.wire``, ``from pkg.parallel import current_mesh``
    the package ``parallel``."""
    with open(os.path.join(PKG, rel), encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=rel)
    here = [PKG_NAME] + rel[:-3].split(os.sep)[:-1]
    inside: set[str] = set()
    outside: set[str] = set()

    def note(dotted: str) -> None:
        parts = dotted.split(".")
        if parts[0] != PKG_NAME:
            outside.add(parts[0])
        elif len(parts) > 1:
            two = ".".join(parts[1:3])
            inside.add(two if _is_module(two) else parts[1])

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                note(alias.name)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = here[: len(here) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            for alias in node.names:
                full = f"{base}.{alias.name}".split(".")
                submodule = full[0] == PKG_NAME and _is_module(".".join(full[1:]))
                note(".".join(full) if submodule else base)
    return inside, outside


def test_the_table_names_the_units_on_disk():
    assert set(ALLOWED) == set(UNITS), (
        "a subpackage or top-level module without a row, or a row "
        f"without one: {sorted(set(ALLOWED) ^ set(UNITS))}"
    )
    named = {u for pair in ONLY_MODULES for u in pair}
    named |= {u for f, m in UPWARD for u in (_unit_of(f), m.split(".")[0])}
    assert named <= set(UNITS), sorted(named - set(UNITS))


@pytest.mark.parametrize("unit", sorted(UNITS))
def test_unit_imports_stay_inside_the_table(unit):
    allowed = ALLOWED[unit]
    if unit in NUMERIC:
        assert not allowed & SYSTEMS
    seen_upward = set()
    problems = []
    for rel in UNITS[unit]:
        inside, outside = _imports(rel)
        for top in sorted(outside & OUTSIDE):
            problems.append(f"{rel} imports {top}, which is built on the package")
        for module in sorted(inside):
            target = module.split(".")[0]
            if target == unit:
                continue
            key = (rel.replace(os.sep, "/"), module)
            if key in UPWARD:
                seen_upward.add(key)
                if unit in NUMERIC and target in SYSTEMS:
                    problems.append(f"{rel} -> {module}: numeric code reaches a system")
                continue
            only = ONLY_MODULES.get((unit, target))
            if target not in allowed:
                problems.append(f"{rel} -> {module}: {unit} may not import {target}")
            elif only is not None and module not in only:
                problems.append(
                    f"{rel} -> {module}: of {target}, {unit} takes only {sorted(only)}"
                )
    repaired = sorted(
        k for k in UPWARD if _unit_of(k[0]) == unit and k not in seen_upward
    )
    assert not problems, "\n".join(problems)
    assert not repaired, f"UPWARD lists edges that are gone; strike them: {repaired}"
