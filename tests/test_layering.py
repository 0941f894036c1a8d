"""The package's layering, held by its import graph.

Every arrow between two modules of ``torchft_tpu`` points down this table or
stays inside a row.  Imports are read with ``ast`` over each whole file, so a
lazy import inside a function counts like one at the top, and nothing is
imported.  What ``ast`` cannot see: the string maps behind the lazy
``__getattr__`` of the package ``__init__`` files (each sits in the row of
the highest module it names), and a module reached through ``sys.path``
under a bare name (``drill.py`` imports ``scripts/flight_merge.py`` that
way: ROADMAP D10).

The rows are ``PERF.md`` section 3's layers, bottom to top, with the shared
plumbing under them.  A name ending in ``.*`` is a package with everything
in it.  A new module needs a row here; a module that has to import upward is
in the wrong row, or the code it wants is.
"""

from __future__ import annotations

import ast
import functools
import os
from typing import Dict, Iterator, List, Tuple

import pytest

PACKAGE = "torchft_tpu"
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the bottom layer first: a module imports from its own row and from rows
# listed before it, never from a row listed after it (a higher layer)
ROWS: List[Tuple[str, Tuple[str, ...]]] = [
    ("options", ("knobs",)),
    (
        "wire-records-serialization",
        (
            "wire",
            "futures",
            "work",
            "obs.*",
            "observability",
            "checkpointing._rwlock",
            "checkpointing.serialization",
            "checkpointing.transport",
            "utils.*",
        ),
    ),
    ("store-kernels-data", ("store", "ops.*", "data")),
    (
        # the model code: kernels under it, no Manager, no communicator
        "compiled-step-models",
        (
            "parallel.mesh",
            "parallel.ring_attention",
            "parallel.moe",
            "parallel.pipeline",
            "models.*",
        ),
    ),
    (
        "host-data-plane",
        (
            "communicator",
            "native",
            "quantization",
            "coord",
            "coord.aggregator",
            "multiprocessing",
        ),
    ),
    (
        "collectives-lighthouse-heal-transports",
        (
            "collectives",
            "lighthouse",
            "checkpointing",
            "checkpointing.http_transport",
            "checkpointing.comm_transport",
            "baby",
            "parameter_server",
        ),
    ),
    ("control-plane-servers", ("manager_server", "tier", "coord.scale")),
    ("manager", ("manager",)),
    ("device-host-boundary", ("ddp", "optim")),
    (
        # hsdp and degraded import each other: one row, no exception list
        "replica-algorithms-trainers",
        (
            "local_sgd",
            "spare",
            "parallel",
            "parallel.hsdp",
            "parallel.degraded",
            "parallel.rehearsal",
        ),
    ),
    (
        "entry-points-drills-lint",
        (
            "",  # the package's own __init__: a lazy facade over everything
            "drill",
            "chaos",
            "launcher",
            "punisher",
            "scheduler",
            "coordination",
            "analysis.*",
        ),
    ),
]

# what lives beside the package and is never imported from inside it
OUTSIDE = (
    "ftbench",
    "tests",
    "examples",
    "scripts",
    "benchmarks",
    "bench",
    "chip_smoke",
    "__graft_entry__",
)


@functools.lru_cache(maxsize=None)
def _modules() -> Dict[str, str]:
    """Module name relative to the package ('' is its ``__init__``) -> path."""
    out: Dict[str, str] = {}
    top = os.path.join(_ROOT, PACKAGE)
    for folder, _dirs, files in os.walk(top):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            parts = os.path.relpath(path, top)[: -len(".py")].split(os.sep)
            if parts[-1] == "__init__":
                parts = parts[:-1]
            out[".".join(parts)] = path
    return out


def _imports(module: str, path: str) -> Iterator[Tuple[str, str, int]]:
    """(absolute module named, attribute or '', line) for every import."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    here = [PACKAGE] + ([p for p in module.split(".") if p])
    if not path.endswith("__init__.py"):
        here = here[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, "", node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = here[: len(here) - (node.level - 1)]
                base = ".".join(up + ([node.module] if node.module else []))
            for alias in node.names:
                yield base, alias.name, node.lineno


def _matches(module: str, pattern: str) -> bool:
    if pattern.endswith(".*"):
        return module == pattern[:-2] or module.startswith(pattern[:-1])
    return module == pattern


def _rows_of(module: str) -> List[int]:
    return [i for i, (_n, pats) in enumerate(ROWS) if any(_matches(module, p) for p in pats)]


@functools.lru_cache(maxsize=None)
def _inner_edges() -> List[Tuple[str, int, str]]:
    """(importing module, line, imported module), both inside the package."""
    modules = _modules()
    edges = []
    for module, path in sorted(modules.items()):
        for base, attr, line in _imports(module, path):
            if base.split(".")[0] != PACKAGE:
                continue
            rel = base[len(PACKAGE) :].lstrip(".")
            target = f"{rel}.{attr}".lstrip(".") if attr else rel
            if target not in modules:
                target = rel  # a name taken from a module, not a module
            while target not in modules:  # e.g. ``import torchft_tpu.x.y`` of a name
                target = target.rpartition(".")[0]
            if target != module:
                edges.append((module, line, target))
    return edges


def _label(module: str) -> str:
    return f"{PACKAGE}.{module}" if module else PACKAGE


@pytest.mark.parametrize("row", range(len(ROWS)), ids=[name for name, _ in ROWS])
def test_no_module_of_a_row_imports_from_a_higher_row(row: int) -> None:
    modules = _modules()
    mine = [m for m in modules if _rows_of(m) == [row]]
    assert mine, f"row {ROWS[row][0]!r} holds no module of the package: {ROWS[row][1]}"
    upward = []
    for module, line, target in _inner_edges():
        if module not in mine:
            continue
        rows = _rows_of(target)
        if rows and max(rows) > row:
            upward.append(
                f"{_label(module)}:{line} imports {_label(target)} "
                f"(row {ROWS[row][0]!r} -> row {ROWS[max(rows)][0]!r})"
            )
    assert not upward, "upward imports:\n" + "\n".join(dict.fromkeys(upward))


def test_the_packages_edge() -> None:
    """Every module has exactly one row, and nothing inside the package
    imports what lives beside it."""
    modules = _modules()
    misplaced = {
        _label(m): [ROWS[i][0] for i in _rows_of(m)] for m in modules if len(_rows_of(m)) != 1
    }
    assert not misplaced, f"modules in no row or in several (add one to ROWS): {misplaced}"
    outward = [
        f"{_label(module)}:{line} imports {base}"
        for module, path in sorted(modules.items())
        for base, _attr, line in _imports(module, path)
        if base.split(".")[0] in OUTSIDE
    ]
    assert not outward, "the package imports what lives beside it:\n" + "\n".join(outward)


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        ("ops.indexed_attention", "store-kernels-data", {"ops.flash_attention"}),  # PR 53: the walk over live blocks
        ("models.indexed_sparse_moe", "compiled-step-models", {"ops.indexed_attention", "parallel.moe", "models.decoder", "obs.spans"}),
    ],
)
def test_indexed_attention_is_model_code_over_kernels(module: str, row: str, may_import: set) -> None:
    """PR 33's two modules: the kernels in the kernels' row, the model in
    the models', importing kernels and model code and nothing of the Manager."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        ("ops.ssd", "store-kernels-data", {"ops.kda"}),
        (
            "models.ssm_hybrid_moe", "compiled-step-models",
            {"ops.ssd", "ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
        (
            "models.ssm_hybrid_dense", "compiled-step-models",
            {"ops.ssd", "ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
    ],
)
def test_the_state_space_scan_is_model_code_over_kernels(module: str, row: str, may_import: set) -> None:
    """PR 35's two modules: the scan's kernels in the kernels' row (sharing
    ``ops/kda.py``'s products), the model in the models', importing kernels
    and model code and nothing of the Manager; PR 69's model over the same
    kernels (``parallel/moe.py`` for the shared SwiGLU alone) and no sibling."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        ("ops.flash_attention", "store-kernels-data", set()),
        (
            "models.windowed_moe", "compiled-step-models",
            {"ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
        # PR 67's model: the same kernels at another window and a GQA group of seven, ``RoutedExperts`` told where
        # its router reads; no sibling model
        (
            "models.prerouted_moe", "compiled-step-models",
            {"ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
    ],
)
def test_windowed_attention_is_model_code_over_the_flash_kernels(module: str, row: str, may_import: set) -> None:
    """PR 41's module and the kernels it made take a window: the kernels in
    the kernels' row, importing nothing of the package; the models in the
    models', importing kernels and model code and nothing of the Manager."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        # the mixer and the prediction module that two models share: model code over the flash kernels
        ("models.latent", "compiled-step-models", {"ops.flash_attention", "models.decoder", "obs.spans"}),
        ("models.ling_hybrid", "compiled-step-models", {"ops.kda", "parallel.moe", "models.decoder", "models.latent", "obs.spans"}),
        (
            "models.latent_moe", "compiled-step-models",
            {"ops.flash_attention", "parallel.moe", "models.decoder", "models.latent", "obs.spans"},
        ),
    ],
)
def test_latent_attention_and_the_prediction_module_are_defined_once_under_both_models(
    module: str, row: str, may_import: set
) -> None:
    """PR 49's two modules and the one it moved code out of: ``models/latent.py``
    holds the MLA mixer and the MTP module, ``LingHybrid`` and ``LatentMoE``
    both import it (neither holds a copy: Ling no longer imports the flash
    kernels itself), and all three are model code, importing kernels and model
    code and nothing of the Manager."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        ("ops.flash_attention", "store-kernels-data", set()),
        (
            "models.eva", "compiled-step-models",
            {"ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
    ],
)
def test_chunk_summary_attention_is_model_code_over_the_flash_kernels(module: str, row: str, may_import: set) -> None:
    """PR 52's module and the entry it made the flash kernels take (two key
    sources under one softmax, a second rule of liveness in the one walk): the
    kernels in the kernels' row, importing nothing of the package; the model
    in the models', calling ``models/decoder.py``'s projections, rope, norm and
    cross-entropy, the shared SwiGLU, and nothing of the Manager."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        ("ops.gdn", "store-kernels-data", {"ops.kda"}),
        (
            "models.gated_delta_moe", "compiled-step-models",
            {"ops.gdn", "ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
    ],
)
def test_gated_delta_net_is_model_code_over_kernels(module: str, row: str, may_import: set) -> None:
    """PR 56's two modules: the scalar-decay delta rule's kernels in the
    kernels' row (sharing ``ops/kda.py``'s products and its triangular inverse
    by import), the model in the models', calling ``models/decoder.py``'s
    convolution, unit length, rope, norm and projections, and nothing of the
    Manager."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        (
            "models.looped", "compiled-step-models",
            {"ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
    ],
)
def test_the_looped_model_is_model_code_over_the_flash_kernels(module: str, row: str, may_import: set) -> None:
    """PR 59's module: a stack of layers applied several times a step is model
    code, calling ``models/decoder.py``'s norm, rope and refusals, the shared
    SwiGLU and the flash kernels as they stand, and nothing of the
    Manager, ``ddp`` or the harness: the loop needed no edit outside it."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


@pytest.mark.parametrize(
    "module,row,may_import",
    [
        ("ops.selscan", "store-kernels-data", {"ops.kda"}),
        (
            "models.sambay", "compiled-step-models",
            {"ops.selscan", "ops.flash_attention", "parallel.moe", "models.decoder", "obs.spans"},
        ),
    ],
)
def test_the_decoder_hybrid_decoder_is_model_code_over_kernels(module: str, row: str, may_import: set) -> None:
    """PR 63's two modules: the selective scan's kernels in the kernels' row
    (sharing ``ops/kda.py``'s products by import), the model in the models',
    calling ``models/decoder.py``'s norms, convolution, projections, refusals,
    layer walk and blocked head, the shared SwiGLU and the flash kernels as
    they stand, no sibling model and nothing of the Manager."""
    assert [ROWS[i][0] for i in _rows_of(module)] == [row]
    assert {target for importer, _line, target in _inner_edges() if importer == module} == may_import


def test_no_model_imports_another_and_the_shared_shell_imports_none() -> None:
    """PR 61's seam, for every module of ``models/`` at once: what models
    share is ``models/decoder.py`` (the shell of a decoder, as functions) and
    ``models/latent.py`` (the MLA mixer and the MTP module of the two models
    that have them), and a model file imports no other module of ``models/``:
    no model is another's library.  ``models/decoder.py`` imports nothing of
    ``models/``.  ``llama_moe.py`` subclasses ``Llama`` (the old top-1 ``MoE``
    that ``ftbench``'s spec test still builds: ROADMAP.md D8) and is the one
    exception, by name."""
    shared = {"models.decoder", "models.latent"}
    sideways = [
        f"{_label(module)}:{line} imports {_label(target)}"
        for module, line, target in _inner_edges()
        if module.startswith("models.") and target.startswith("models.") and target not in shared
        and (module, target) != ("models.llama_moe", "models.llama")
    ]
    assert not sideways, "a model imports a sibling:\n" + "\n".join(dict.fromkeys(sideways))
    assert not [t for m, _line, t in _inner_edges() if m == "models.decoder" and t.startswith("models")]
