"""A census of what can be set.

README.md's section "What can be set" is the one list of the
``PADDLE_TPU_*`` and ``PADDLE_FAULT_*`` variables the package reads.
These tests hold the list to the code in both directions, so a new
variable (or one that stopped being read) shows as a line of a diff
there, and hold the old measurement entry's variables out of the tree.
"""
import ast
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"PADDLE_(?:TPU|FAULT)_[A-Z0-9_]+")


def _sources(*roots):
    for root in roots:
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            for f in sorted(files):
                if f.endswith(".py"):
                    path = os.path.join(dirpath, f)
                    with open(path) as fh:
                        yield os.path.relpath(path, REPO), fh.read()


def _listed():
    with open(os.path.join(REPO, "README.md")) as fh:
        readme = fh.read()
    section = readme.split("\n## What can be set\n", 1)[1]
    section = section.split("\n## ", 1)[0]
    rows = [ln for ln in section.splitlines() if ln.startswith("| `")]
    names = [NAME.search(ln.split("|")[1]).group(0) for ln in rows]
    assert len(names) == len(set(names)), "a name is listed twice"
    for ln in rows:             # name | what it decides | default | kind
        cells = [c.strip() for c in ln.strip("|").split("|")]
        assert len(cells) == 4 and all(cells), ln
        assert cells[3] in ("option", "deployment", "fault"), ln
    return set(names)


def test_every_variable_the_package_names_is_listed_and_read():
    named, read = set(), set()
    for _, src in _sources("paddle_tpu"):
        named.update(NAME.findall(src))
        # a read hands the name over as a string of its own: to
        # os.environ, or to a helper that asks os.environ
        read.update(n.value for n in ast.walk(ast.parse(src))
                    if isinstance(n, ast.Constant)
                    and isinstance(n.value, str)
                    and NAME.fullmatch(n.value))
    listed = _listed()
    assert named - listed == set(), "named in the package, not in README"
    assert listed - read == set(), "in README's list, read nowhere"
    fault = {n for n in listed if n.startswith("PADDLE_FAULT_")}
    assert fault and fault == {
        n for n in read if n.startswith("PADDLE_FAULT_")}


def test_the_old_entrys_variables_occur_nowhere():
    old = re.compile(r"\bBENCH_[A-Z]")
    found = [f"{path}:{i}" for path, src in _sources("paddle_tpu", "tests")
             for i, ln in enumerate(src.splitlines(), 1) if old.search(ln)]
    assert found == []
    assert not os.path.exists(os.path.join(REPO, "bench.py"))
