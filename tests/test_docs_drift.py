"""The documents name only what the tree has.

One case per document an operator is sent to: every ``make <target>``
it names is a target of the ``Makefile``, every path and command it
names exists, and every ``TFT_*`` variable it names is read somewhere
in the program, the benchmark, the tests or ``chip_smoke.py``. Text
only: nothing is imported or run. (The metric and span catalog of
``docs/observability.md`` has its own drift test,
``tests/test_tracing_flight.py::TestDocsDrift``.)
"""

import importlib.util
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

DOCUMENTS = [
    "README.md",
    "docs/api.md",
    "docs/design.md",
    "docs/fault_tolerance.md",
    "docs/index.md",
    "docs/ingest.md",
    "docs/observability.md",
    "docs/pipelines.md",
    "docs/serving_llm.md",
    "docs/tuning.md",
    ".claude/skills/verify/SKILL.md",
    "Makefile",
]

#: where a path a document names may be rooted: `serve/engine.py` is
#: the package's, `configs/gpt2-xl.json` the benchmark's
PATH_ROOTS = ("", "tensorframes_tpu", "chipbench", "tests", "docs")
#: where a `TFT_*` variable must be read for a document to name it
CODE_TREES = ("tensorframes_tpu", "chipbench", "tests")

_TARGET = re.compile(r"^([A-Za-z0-9_.-]+):(?!=)", re.M)
#: `make` as a command: first on a line, behind a backtick, a shell
#: separator or a `VAR=value` prefix ("these make flaky-link ..." is prose)
_MAKE = re.compile(
    r"(?:^\s*|[`;&|]\s*|\b[A-Z_]+=\S*\s+)make\s+([a-z][a-z0-9_-]*)", re.M
)
_TICKED = re.compile(r"`([^`\s]+)`")
_RUN_FILE = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_RUN_MODULE = re.compile(r"\bpython3?\s+-m\s+([A-Za-z_][\w.]*)")
_VARIABLE = re.compile(r"\bTFT_[A-Z0-9_]+")


@pytest.fixture(scope="module")
def targets():
    return set(_TARGET.findall((ROOT / "Makefile").read_text())) - {".PHONY"}


def _text(document):
    text = (ROOT / document).read_text()
    if document == "Makefile":  # its comments are the document
        text = "\n".join(
            ln for ln in text.splitlines() if ln.lstrip().startswith("#")
        )
    return text


def _exists(path):
    return any((ROOT / root / path).exists() for root in PATH_ROOTS)


def _module_exists(module):
    parts = module.split(".")
    here = ROOT.joinpath(*parts)
    if here.with_suffix(".py").exists() or (here / "__init__.py").exists():
        return True
    if (ROOT / parts[0]).exists():
        return False
    # pytest, pip, compileall: installed, not the tree's
    return importlib.util.find_spec(parts[0]) is not None


@pytest.fixture(scope="module")
def variables():
    sources = [ROOT / "chip_smoke.py"]
    for tree in CODE_TREES:
        sources += (ROOT / tree).rglob("*.py")
    me = Path(__file__).resolve()  # names one that nothing reads, below
    return {
        name
        for p in sources
        if p != me
        for name in _VARIABLE.findall(p.read_text())
    }


def stale_names(text, targets, variables):
    """What ``text`` names that the tree has not, as readable lines."""
    stale = []
    for name in _MAKE.findall(text):
        if "-" in name and name not in targets:  # "make sure" is prose
            stale.append(f"make {name}: no such target in the Makefile")
    for name in _TICKED.findall(text):
        path = name.split("::")[0]
        path = re.sub(r":[\d,:-]+$", "", path)  # `file.py:12-40`
        if (
            "/" in path
            and path.endswith((".py", ".json", ".md"))
            and not path.startswith("/")  # /root/reference/...: not ours
            and not re.search(r"[<>*{}$]", path)  # a pattern, not a path
            and not _exists(path)
        ):
            stale.append(f"`{name}`: no such file")
    for path in _RUN_FILE.findall(text):
        if not path.startswith("/") and not _exists(path):
            stale.append(f"python {path}: no such file")
    for module in _RUN_MODULE.findall(text):
        if not _module_exists(module):
            stale.append(f"python -m {module}: no such module")
    for name in _VARIABLE.findall(text):
        if name not in variables:
            stale.append(f"{name}: no *.py of the tree reads it")
    return sorted(set(stale))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_only_what_the_tree_has(document, targets, variables):
    stale = stale_names(_text(document), targets, variables)
    assert not stale, f"{document} names what is gone:\n  " + "\n  ".join(stale)


def test_the_scanner_sees_each_kind_of_stale_name(targets, variables):
    """The clauses above pass on an empty match too; this holds each of
    them to one name that must be flagged and one that must not."""
    gone = (
        "`make no-such-target`, these make flaky-link, `serve/no_such.py:12`, "
        "`/root/reference/x/y.py`, `<dir>/<proc-id>.json`, `manifest.json`, "
        "python no_such.py, python3 -m chipbench.no_such, python -m pytest, "
        "`TFT_NO_SUCH_VARIABLE`, `TFT_TUNE=0 make gone-too`, `make lint`, "
        "`serve/engine.py::_emit`, python chip_smoke.py"
    )
    assert stale_names(gone, targets, variables) == [
        "TFT_NO_SUCH_VARIABLE: no *.py of the tree reads it",
        "`serve/no_such.py:12`: no such file",
        "make gone-too: no such target in the Makefile",
        "make no-such-target: no such target in the Makefile",
        "python -m chipbench.no_such: no such module",
        "python no_such.py: no such file",
    ]


def test_lint_compiles_only_paths_that_exist():
    recipe = re.search(
        r"^lint:\n\t.*compileall\s+-q\s+(.*)$", (ROOT / "Makefile").read_text(), re.M
    )
    assert recipe, "the Makefile's lint target no longer runs compileall"
    named = recipe.group(1).split()
    assert named and [p for p in named if not (ROOT / p).exists()] == []
