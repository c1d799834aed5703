"""What ``benchmark/`` takes from the program, stated where tier-1 runs.

``benchmark/`` is the one instrument that measures this system, and a
refactoring PR may not edit it. It reaches into ``predictionio_tpu`` by
imports (most of them inside functions), by the driver a traffic file
names and by ``PIO_*`` variables its configurations pin. Nothing here
runs a cell: the tests import, walk syntax trees and read JSON, so that
a PR which moves a name the instrument uses learns of it here and not
on the chip. ``benchmark/tests`` is not run from here.
"""

import ast
import importlib
import inspect
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmark")
PROGRAM = "predictionio_tpu"

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


def _modules(*subdirs):
    return [f"benchmark.{d}.{f[:-3]}" for d in subdirs
            for f in sorted(os.listdir(os.path.join(BENCH_DIR, d)))
            if f.endswith(".py") and f != "__init__.py"]


RUN_MODULES = _modules("drivers", "harness", "models", "stores") + [
    "benchmark.run", "benchmark.find_knee"]


@pytest.mark.parametrize("module", RUN_MODULES)
def test_module_imports_on_the_cpu(module):
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    importlib.import_module(module)


def test_every_per_layer_metric_has_its_reader():
    from benchmark.harness import cell as cells

    readers = _modules("layer_metrics")
    for module in readers:
        assert callable(importlib.import_module(module).read), module
    declared = [m["name"] for m in BENCHMARK["per_layer"]]
    missing = [n for n in declared if cells.load_layer_metric(n) is None]
    assert not missing, missing
    unread = sorted({m.rsplit(".", 1)[1] for m in readers} - set(declared))
    assert not unread, f"readers no per_layer entry names: {unread}"


def _non_test_sources():
    for folder, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in ("tests", "__pycache__")]
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(folder, f)


def _of_program(dotted):
    return dotted == PROGRAM or dotted.startswith(PROGRAM + ".")


def _names_used(path):
    """Every dotted name of the program one file reaches for: what it
    imports, at the top or inside a function, and every attribute chain
    that starts at such an import's local name."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    local, used = {}, set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level == 0 \
                and _of_program(node.module or ""):
            for a in node.names:
                used.add((f"{node.module}.{a.name}", ()))
                local[a.asname or a.name] = f"{node.module}.{a.name}"
        elif isinstance(node, ast.Import):
            for a in node.names:
                if _of_program(a.name):
                    used.add((a.name, ()))
                    local[a.asname or PROGRAM] = \
                        a.name if a.asname else PROGRAM
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            chain, base = [node.attr], node.value
            while isinstance(base, ast.Attribute):
                chain.append(base.attr)
                base = base.value
            if isinstance(base, ast.Name) and base.id in local:
                used.add((local[base.id], tuple(reversed(chain))))
    return used


def _resolve(dotted, chain):
    """The dotted names that resolved, then the first that did not (or
    None). A chain is followed through modules and classes only: what
    an instance or a call returns is not the program's static surface."""
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
            break
        except ModuleNotFoundError:
            continue
    else:
        return [], dotted
    found, name = [], ".".join(parts[:cut])
    for attr in tuple(parts[cut:]) + chain:
        if not (inspect.ismodule(obj) or inspect.isclass(obj)):
            break
        name += "." + attr
        if not hasattr(obj, attr):
            return found, name
        obj = getattr(obj, attr)
        found.append(name)
    return found or [name], None


def test_every_name_the_benchmark_takes_from_the_program_resolves():
    surface, unresolved = set(), []
    for path in _non_test_sources():
        for dotted, chain in sorted(_names_used(path)):
            found, lost = _resolve(dotted, chain)
            surface.update(found)
            if lost:
                unresolved.append(
                    f"{os.path.relpath(path, REPO)}: {lost}")
    assert surface, "the walk found no import of the program at all"
    assert not unresolved, (
        "benchmark/ uses names the program no longer has:\n  "
        + "\n  ".join(unresolved)
        + f"\nthe {len(surface)} names that do resolve (the surface):\n  "
        + "\n  ".join(sorted(surface)))


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_has_its_files_and_its_driver(workload):
    from benchmark.harness import cell as cells

    for rehearse in (False, True):
        cell = cells.load_cell(workload, rehearse=rehearse)
        assert cell.config and cell.traffic and cell.end_to_end
        assert callable(cells.load_driver(cell.traffic["kind"]))


def test_every_variable_a_configuration_pins_is_read_by_the_program():
    pinned = set()
    for entry in BENCHMARK["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            config = json.load(f)
        for env in (config.get("env", {}),
                    config.get("rehearse", {}).get("env", {})):
            pinned.update(k for k in env if k.startswith("PIO_"))
    assert pinned, "no configuration sets a PIO_* variable any more"
    source = []
    for folder, _, files in os.walk(os.path.join(REPO, PROGRAM)):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    source.append(fh.read())
    source = "\n".join(source)
    unread = sorted(n for n in pinned
                    if f'"{n}"' not in source and f"'{n}'" not in source)
    assert not unread, (
        f"configurations pin {sorted(pinned)}; the program reads "
        f"no {unread}")


def test_no_tpu_no_result():
    """A number from the CPU is never printed under a device metric's
    name: a run that is not a rehearsal and finds no TPU exits 1 before
    it measures, with no result line."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rec-ml20m.train"], cwd=REPO, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 1, out.stderr[-2000:]
    assert "{" not in out.stdout
