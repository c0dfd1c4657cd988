"""The package's import graph, read with `ast` (nothing here imports JAX or
the package, but for the family table's test, which resolves the table's
import paths as the factory does).

  * each sub-package imports only from the sub-packages below it: the
    allowed sets are the arrows as they stand, and the two arrows that
    point the wrong way are named by file, so that a third cannot appear
    unnoticed and a paid debt must be struck here; the one module every
    part may import is `metrics/trace.py`, the run's recorder, which
    imports nothing but the standard library (held here too);
  * inside `models/` a family's file imports the shared modules
    (`layers.py`, `mixers.py`, `staged.py`) and no other family's file: a
    new family, or a change to a shared mixer, opens no older family;
  * every `model_family` of the registry has one record in the family
    table (`configs/families.py`), and every record's paths resolve;
  * nothing under `solvingpapers_tpu/` imports what stands beside it
    (the benchmark, the tools, the tests, the chip check);
  * every `solvingpapers_tpu` module that the benchmark imports exists,
    and binds the names the benchmark takes from it: a deletion cannot
    break the benchmark before the chip says so.
"""

import ast
import pathlib

import pytest

pytestmark = pytest.mark.fast

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = "solvingpapers_tpu"

# sub-package -> what it may import from the package (itself aside)
ALLOWED = {
    "kernels": set(),
    "ops": {"kernels"},
    "sharding": {"kernels", "ops"},
    "data": {"native", "sharding"},
    "checkpoint": set(),
    "metrics": {"ops", "sharding"},
    "infer": {"ops", "sharding"},
    "models": {"infer", "kernels", "ops", "sharding"},
    "train": {"checkpoint", "metrics", "ops", "sharding"},
    "serve": {"buildinfo", "infer", "metrics", "models", "ops"},
    # `metrics`: the family table names `mfu.looped_flops_per_token`
    "configs": {"data", "metrics", "models", "sharding", "train"},
}

# the arrows that point upwards today (ROADMAP D17, D18): (file, target)
BACK_ARROWS = {
    "metrics": {("metrics/timeseries.py", "serve")},
    "infer": {("infer/speculative.py", "models")},
}

# below everything: the run's recorder, which start-up's spans are written to
# from every part (each `__init__.py`'s `import:<package>`, `create_mesh`, ...)
BOTTOM = f"{PKG}.metrics.trace"

BESIDE_THE_PACKAGE = {"benchmarks", "tools", "tests", "bench", "chip_smoke"}


def imports_of(path: pathlib.Path, package: tuple[str, ...] = ()):
    """(absolute dotted module, names taken from it) for every import
    statement of the file, wherever it stands; `package` is the file's own
    package, which relative imports are resolved against."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, ()
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - (node.level - 1)] if node.level else ()
            module = ".".join((*base, *filter(None, [node.module])))
            yield module, tuple(alias.name for alias in node.names)


def package_files(sub: str = ""):
    """(file, the package it belongs to) under the package or one part of it."""
    for path in sorted((REPO / PKG / sub).rglob("*.py")):
        yield path, path.relative_to(REPO).parts[:-1]


def package_imports(sub: str):
    """(file relative to the package, first segment under the package) for
    every import a sub-package makes of another part of the package."""
    root = REPO / PKG
    for path, package in package_files(sub):
        for module, names in imports_of(path, package):
            parts = module.split(".")
            if parts[0] != PKG or module == BOTTOM:
                continue
            # `from solvingpapers_tpu import serve` names its target last
            targets = [parts[1]] if len(parts) > 1 else names
            for target in targets:
                if target != sub:
                    yield path.relative_to(root).as_posix(), target


@pytest.mark.parametrize("sub", sorted(ALLOWED))
def test_subpackage_imports_only_from_below(sub):
    upwards = {(f, t) for f, t in package_imports(sub) if t not in ALLOWED[sub]}
    assert upwards == BACK_ARROWS.get(sub, set())


# `models/`: what every family may import, and the three pipeline twins,
# which import the family they stage (ROADMAP D4: one staged decoder for
# the three would end the exception)
SHARED_MODELS = {"__init__", "layers", "mixers", "staged"}
PIPE_TWINS = {"gpt_pipe": "gpt", "llama3_pipe": "llama3",
              "deepseekv3_pipe": "deepseekv3"}
FAMILY_MODULES = sorted(
    path.stem for path in (REPO / PKG / "models").glob("*.py")
    if path.stem not in SHARED_MODELS)


def models_imported_by(stem: str) -> set[str]:
    """The modules of `models/` that `models/<stem>.py` imports."""
    path = REPO / PKG / "models" / f"{stem}.py"
    prefix = (PKG, "models")
    found = set()
    for module, names in imports_of(path, prefix):
        parts = tuple(module.split("."))
        if parts[:2] != prefix:
            continue
        # `from solvingpapers_tpu.models import ouro` names its target last
        found.update([parts[2]] if len(parts) > 2 else names)
    return found


@pytest.mark.parametrize("stem", FAMILY_MODULES)
def test_family_module_imports_no_other_family(stem):
    assert len(FAMILY_MODULES) >= 16
    allowed = SHARED_MODELS | {stem, PIPE_TWINS.get(stem, stem)}
    assert models_imported_by(stem) <= allowed


@pytest.mark.parametrize("stem", sorted(SHARED_MODELS - {"__init__"}))
def test_shared_models_module_imports_no_family(stem):
    assert models_imported_by(stem) <= SHARED_MODELS


def registry_families() -> list[str]:
    """Every `model_family="..."` that `configs/registry.py` states."""
    tree = ast.parse((REPO / PKG / "configs" / "registry.py").read_text())
    return sorted({
        kw.value.value for node in ast.walk(tree)
        if isinstance(node, ast.Call) for kw in node.keywords
        if kw.arg == "model_family" and isinstance(kw.value, ast.Constant)})


REGISTRY_FAMILIES = registry_families()


@pytest.mark.parametrize("family", REGISTRY_FAMILIES)
def test_family_of_the_registry_has_a_record_that_resolves(family):
    from solvingpapers_tpu.configs.families import FAMILIES, resolve

    assert len(REGISTRY_FAMILIES) >= 17
    # no record without a preset either: a dead record is a dead family
    assert sorted(FAMILIES) == REGISTRY_FAMILIES
    record = FAMILIES[family]
    assert isinstance(resolve(record.model), type)
    assert callable(resolve(record.objective))
    assert record.init_fn is None or callable(resolve(record.init_fn))
    assert record.flops_per_token is None or callable(record.flops_per_token)
    assert record.unservable is None or (
        len(record.unservable.split()) >= 8 and "ROADMAP" in record.unservable)


def test_the_bottom_module_imports_the_standard_library_only():
    """At module level; a function of `metrics/trace.py` may import what
    its caller has loaded (JAX's annotation, a formatter of `hlo_cost`)."""
    import sys

    path = REPO.joinpath(*BOTTOM.split(".")).with_suffix(".py")
    top_level = [
        alias.name if isinstance(node, ast.Import) else node.module
        for node in ast.parse(path.read_text()).body
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in (node.names if isinstance(node, ast.Import) else [None])
    ]
    assert top_level
    assert {m.split(".")[0] for m in top_level} <= set(sys.stdlib_module_names)


def test_package_imports_nothing_that_stands_beside_it():
    found = [
        (path.relative_to(REPO).as_posix(), module)
        for path, package in package_files()
        for module, _ in imports_of(path, package)
        if module.split(".")[0] in BESIDE_THE_PACKAGE
    ]
    assert found == []


def benchmark_imports() -> dict[str, set[str]]:
    """module -> names, over every file under `benchmarks/`."""
    taken: dict[str, set[str]] = {}
    for path in sorted((REPO / "benchmarks").rglob("*.py")):
        for module, names in imports_of(path):
            if module.split(".")[0] == PKG:
                taken.setdefault(module, set()).update(names)
    return taken


def module_file(module: str) -> pathlib.Path | None:
    stem = REPO.joinpath(*module.split("."))
    for candidate in (stem.with_suffix(".py"), stem / "__init__.py"):
        if candidate.is_file():
            return candidate
    return None


def bound_at_top_level(path: pathlib.Path) -> set[str]:
    bound = set()
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            bound.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            bound.update((a.asname or a.name).split(".")[0] for a in node.names)
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            bound.update(n.id for t in targets for n in ast.walk(t)
                         if isinstance(n, ast.Name))
    return bound


BENCHMARK_IMPORTS = benchmark_imports()


def test_the_benchmark_imports_the_package():
    assert len(BENCHMARK_IMPORTS) >= 10


@pytest.mark.parametrize("module", sorted(BENCHMARK_IMPORTS))
def test_module_the_benchmark_imports_is_in_the_tree(module):
    path = module_file(module)
    assert path is not None, f"benchmarks/ imports {module}: no such file"
    bound = bound_at_top_level(path)
    missing = {
        name for name in BENCHMARK_IMPORTS[module]
        if name not in bound and module_file(f"{module}.{name}") is None
    }
    assert not missing, f"benchmarks/ takes {sorted(missing)} from {module}"
