"""The repo's records agree with the tree: every declared option has a
reader, and the documents name files that exist."""

import functools
import inspect
import os
import re

import pytest

from modin_tpu.config import envvars

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: where a reader of an option may live (the root's own scripts besides)
CODE_DIRS = ("modin_tpu", "tests", "scripts", "benchmark", "asv_bench")

OPTIONS = sorted(
    (cls.varname, name)
    for name, cls in vars(envvars).items()
    if inspect.isclass(cls)
    and issubclass(cls, envvars.EnvironmentVariable)
    and getattr(cls, "varname", None)
)

DOCUMENTS = (
    "README.md",
    "docs/usage.md",
    "docs/observability.md",
    "docs/architecture.md",
    "docs/configuration.md",
    "scripts/check_all.sh",
    ".claude/skills/verify/SKILL.md",
)

#: a repo-relative path of a kind the repo holds; a placeholder
#: (``<dir>/x.json``, ``BENCH_*.json``, ``$TMP/x.json``) is not one
_PATH = re.compile(
    r"(?<![\w./<>*{}$-])([\w.-]+(?:/[\w.-]+)*\.(?:py|sh|json))(?![\w/*<{])"
)
_COMMAND = re.compile(r"^\s*(?:\w+=\S+\s+)*(?:python3?|chiprun|bash|sh|\./)")


@functools.lru_cache(maxsize=None)
def _code_outside_config():
    """The text of every ``.py`` / ``.sh`` file outside ``modin_tpu/config/``."""
    paths = [
        os.path.join(ROOT, name)
        for name in os.listdir(ROOT)
        if name.endswith((".py", ".sh"))
    ]
    for top in CODE_DIRS:
        for folder, _dirs, files in os.walk(os.path.join(ROOT, top)):
            if os.path.relpath(folder, ROOT).startswith("modin_tpu/config"):
                continue
            paths += [
                os.path.join(folder, name)
                for name in files
                if name.endswith((".py", ".sh"))
            ]
    texts = []
    for path in paths:
        with open(path, encoding="utf-8", errors="replace") as handle:
            texts.append(handle.read())
    return "\n".join(texts)


@pytest.mark.parametrize("varname, class_name", OPTIONS, ids=[v for v, _ in OPTIONS])
def test_every_option_has_a_reader(varname, class_name):
    """An option nothing names outside ``modin_tpu/config/`` is a switch
    that switches nothing: it goes, or its reader comes with it."""
    code = _code_outside_config()
    assert varname in code or re.search(rf"\b{class_name}\b", code), (
        f"{class_name} ({varname}) is declared in config/envvars.py and "
        "named in no .py or .sh file outside modin_tpu/config/"
    )


def _named_paths(document, text):
    """Paths in backticks or on a command line (a shell script: anywhere)."""
    if document.endswith(".sh"):
        return set(_PATH.findall(text))
    fenced = re.findall(r"```.*?```", text, re.S)
    prose = re.sub(r"```.*?```", "", text, flags=re.S)
    spans = re.findall(r"`([^`\n]+(?:\n[^`\n]+)?)`", prose)
    spans += [
        line for block in fenced for line in block.splitlines() if _COMMAND.match(line)
    ]
    return {path for span in spans for path in _PATH.findall(span)}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_files_that_exist(document):
    """A path is looked for from the root, from the package, from
    ``benchmark/`` and from the document's own directory."""
    with open(os.path.join(ROOT, document), encoding="utf-8") as handle:
        named = _named_paths(document, handle.read())
    assert named, f"{document} names no file at all: the reader of this test is broken"
    bases = ("", "modin_tpu", "benchmark", os.path.dirname(document))
    missing = sorted(
        path
        for path in named
        if not any(os.path.exists(os.path.join(ROOT, base, path)) for base in bases)
    )
    assert not missing, f"{document} names files that do not exist: {missing}"
