"""Guards against the two debts PR 30 removed growing back: a registered
knob whose readers are gone, and a document that sends its reader to a
file that is gone (reference analog: ps-lite's ``Environment::Get`` keys
were documented nowhere and checked by nothing, ``postoffice.cc:18-31``)."""

import ast
import functools
import os
import re

import pytest

from dt_tpu.analysis.rules_project import _env_reads
from dt_tpu.config import ENV_REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: where a knob's reader may live: dtlint's scope (DT005's dead-row arm)
#: and what it leaves out, the benchmark and the chip's smoke run
READER_SCOPE = ("dt_tpu", "tools", "examples", "benchmark", "chip_smoke.py",
                "__graft_entry__.py")


def _python_files():
    for rel in READER_SCOPE:
        full = os.path.join(ROOT, rel)
        if os.path.isfile(full):
            yield full
        for dirpath, dirnames, names in os.walk(full):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            yield from (os.path.join(dirpath, n) for n in names
                        if n.endswith(".py"))


def test_every_registered_knob_has_a_reader():
    """Each ``ENV_REGISTRY`` row is read (``os.environ``, ``os.getenv`` or
    the ``config.env`` accessors, by literal name) in a program file: a row
    left behind by a deleted reader fails here.  The count is a ceiling: a
    knob is added with a reason (ROADMAP D7), not by the way."""
    read = set()
    for path in _python_files():
        with open(path, encoding="utf-8") as f:
            read.update(name for name, _ in _env_reads(ast.parse(f.read())))
    assert sorted(set(ENV_REGISTRY) - read) == []
    assert len(ENV_REGISTRY) <= 64, len(ENV_REGISTRY)


DOCS = ["README.md", "CLAUDE.md"] + sorted(
    "docs/" + n for n in os.listdir(os.path.join(ROOT, "docs"))
    if n.endswith(".md"))

#: a back-quoted token that looks like a path of this repository: under one
#: of its directories, or a bare program, record or document name
_PATH = re.compile(
    r"^(?:(?:tools|examples|dt_tpu|benchmark|tests|docs)/[\w./-]*\w"
    r"|\w[\w.-]*\.(?:py|json|jsonl|md))$")

#: names a document uses that are not files of the checkout
_NOT_OURS = {
    "tools/old_bench.py",       # docs/dtlint_rules.md: a baseline example
    "manifest.jsonl",           # written under DT_BLACKBOX_DIR at run time
    "tools/launch.py", "executor_group.py",     # the reference's own files
}


@functools.lru_cache(maxsize=None)
def _basenames():
    """Every file name in the checkout: a document may name a module by its
    base name (`trainer.py` for `dt_tpu/training/trainer.py`)."""
    out = set()
    for _, dirnames, names in os.walk(ROOT):
        dirnames[:] = [d for d in dirnames if not d.startswith(".")]
        out.update(names)
    return out


def _named_paths(text):
    for token in re.findall(r"`([^`\n]+)`", text):
        # `tools/dtlint.py --select DT006`, `benchmark/run.py:78`,
        # `tests/test_x.py::test_y`: the path is the first word, up to a colon
        word = token.split()[0].split(":")[0] if token.split() else ""
        if _PATH.match(word) and ".." not in word:
            yield word


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_paths_that_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        named = set(_named_paths(f.read()))
    missing = sorted(p for p in named - _NOT_OURS
                     if not os.path.exists(os.path.join(ROOT, p))
                     and ("/" in p or p not in _basenames()))
    assert missing == [], f"{doc} names paths that do not exist: {missing}"
