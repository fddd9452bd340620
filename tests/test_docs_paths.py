"""The documents name only what exists: every repository path and every
config option that ``README.md``, ``docs/*.md``, the ``Makefile``,
``pyproject.toml`` and the verify skill write must be in the tree.

A deleted script, a renamed field or a retired ``make`` target then fails
here, in the document that still names it, instead of in a reader's shell.
"""

import dataclasses
import functools
import os
import re

import pytest

from stoke_tpu import configs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    ["README.md"]
    + sorted(
        os.path.join("docs", f)
        for f in os.listdir(os.path.join(REPO, "docs"))
        if f.endswith(".md")
    )
    + ["Makefile", "pyproject.toml", ".claude/skills/verify/SKILL.md"]
)

#: names that are not files of this tree: stand-ins for the reader's own
#: file, the reference project's files that the documents cite by line, and
#: a marker file a run writes
NOT_OURS = {
    "my_train.py", "your_script.py", "worker.py",
    "stoke.py", "distributed.py", "extensions.py",
    "docs/Quick-Start.md", "docs/Launchers.md",
    "QUARANTINED.json",
}

_SUFFIX = r"(?:py|sh|md|json|jsonl|yaml|toml|cpp)"
#: a path under one of the repository's directories
_ROOTED = re.compile(
    r"(?<![\w/.<>*-])((?:scripts|stoke_tpu|tests|examples|benchmark|docs"
    r"|docker)/[\w./-]*\w\." + _SUFFIX + r")\b"
)
#: a bare file name (nothing path-like before it)
_BARE = re.compile(
    r"(?<![\w/.<>*{}-])([A-Za-z_][\w-]*\.(?:py|sh|md|jsonl|json))\b"
)


def _read(doc):
    with open(os.path.join(REPO, doc)) as f:
        return f.read()


def _ignored_dirs():
    """Directory names ``.gitignore`` lists: scratch a session may leave on
    disk that the committed tree does not hold."""
    names = {".git"}
    for line in _read(".gitignore").splitlines():
        line = line.strip()
        if line.endswith("/"):
            names.add(line.strip("/"))
    return names


@functools.lru_cache(maxsize=None)
def _tree_basenames():
    skip = _ignored_dirs()
    found = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        found.update(files)
    return frozenset(found)


def _missing_paths(doc, text):
    here = os.path.dirname(doc)
    missing = []
    for path in _ROOTED.findall(text):
        if path not in NOT_OURS and not os.path.exists(
            os.path.join(REPO, path)
        ):
            missing.append(path)
    for name in _BARE.findall(text):
        stem, ext = os.path.splitext(name)
        if name in NOT_OURS:
            continue
        if ext in (".json", ".jsonl"):
            # the repository's records live at the root under upper-case
            # names; a lower-case name is a file a run writes
            if re.fullmatch(r"[A-Z][A-Z0-9_]*(_r\d+)?", stem) and not (
                os.path.exists(os.path.join(REPO, name))
            ):
                missing.append(name)
        elif name not in _tree_basenames() and not os.path.exists(
            os.path.join(REPO, here, name)
        ):
            missing.append(name)
    return sorted(set(missing))


def _registered_markers():
    block = re.search(r"markers = \[(.*?)\n\]", _read("pyproject.toml"), re.S)
    return set(re.findall(r'^\s*"(\w+):', block.group(1), re.M))


def _markers_tests_use():
    used = set()
    tests = os.path.join(REPO, "tests")
    for root, _, files in os.walk(tests):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    used.update(re.findall(r"pytest\.mark\.(\w+)", fh.read()))
    return used


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_paths_that_exist(doc):
    text = _read(doc)
    assert _missing_paths(doc, text) == []
    if doc == "Makefile":
        # every marker a target selects is registered, every target the
        # .PHONY line declares has a recipe
        selected = set(re.findall(r"pytest[^\n]* -m (\w+)", text))
        assert selected <= _registered_markers()
        phony = re.search(r"^\.PHONY: (.*)$", text, re.M).group(1).split()
        targets = set(re.findall(r"^([\w-]+):", text, re.M))
        assert set(phony) <= targets
    if doc == "pyproject.toml":
        # a registered marker that no test carries names nothing
        assert _registered_markers() <= _markers_tests_use()


def _config_classes():
    return {
        name: cls
        for name, cls in vars(configs).items()
        if isinstance(cls, type) and dataclasses.is_dataclass(cls)
    }


def _call_keywords(text, start):
    """Keyword names at the top level of the call whose ``(`` is at
    ``start``; stops at the matching ``)`` (or the end of the text)."""
    depth, names, i = 0, [], start
    while i < len(text):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                break
        elif depth == 1:
            m = re.match(r"(\w+)\s*=(?!=)", text[i:])
            if m and (text[i - 1] in "(, \n\t"):
                names.append(m.group(1))
                i += m.end() - 1
        i += 1
    return names


def _unknown_options(text):
    classes = _config_classes()
    unknown = []
    for m in re.finditer(r"\b([A-Z]\w*Config)\b(?:\.([a-z_]\w*)|(\())", text):
        cls = classes.get(m.group(1))
        if cls is None:
            continue
        fields = {f.name for f in dataclasses.fields(cls)}
        if m.group(2):
            named = [m.group(2)]
        else:
            named = _call_keywords(text, m.end() - 1)
        for name in named:
            if name not in fields and not hasattr(cls, name):
                unknown.append(f"{m.group(1)}.{name}")
    return sorted(set(unknown))


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_options_that_exist(doc):
    assert _unknown_options(_read(doc)) == []
