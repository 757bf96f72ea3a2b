"""Knob census: the documents name the environment knobs the code reads, and
no others. CLAUDE.md's Knobs section is held to `cometbft_tpu/` the way
`trace.NAMES` holds PERF.md's span table, so the next knob is a visible diff
in both places.

A documented `CMTPU_X*` stands for every name with that prefix; a name the
code completes at run time (or writes as `CMTPU_X_*` in a comment) ends in
`_` and is matched as a prefix too."""

import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parent.parent
KNOB = re.compile(r"CMTPU_[A-Z0-9_]+\*?")
# where a documented knob may be read
READERS = ("cometbft_tpu", "benchmarks", "tests", "chip_smoke.py")


def _names(text: str) -> set[str]:
    return set(KNOB.findall(text))


def _names_in_code(*roots: str) -> set[str]:
    found = set()
    this_file = pathlib.Path(__file__).resolve()
    for root in roots:
        path = ROOT / root
        files = [path] if path.is_file() else sorted(path.rglob("*.py"))
        for f in files:
            if f != this_file:
                found |= _names(f.read_text())
    return found


def _is_prefix(name: str) -> bool:
    return name.endswith(("*", "_"))


def _covers(documented: str, name: str) -> bool:
    """Does the documents' `documented` account for the code's `name`?"""
    if _is_prefix(documented):
        return name.startswith(documented.rstrip("*"))
    if _is_prefix(name):
        return documented.startswith(name.rstrip("*"))
    return documented == name


def _knobs_section() -> str:
    text = (ROOT / "CLAUDE.md").read_text()
    section = text.split("\n## Knobs\n", 1)[1]
    return section.split("\n## ", 1)[0]


def test_every_documented_knob_is_read():
    documented = _names((ROOT / "CLAUDE.md").read_text()) | _names(
        (ROOT / "README.md").read_text()
    )
    read = _names_in_code(*READERS)
    unread = sorted(d for d in documented if not any(_covers(d, r) for r in read))
    assert not unread, f"documented, but read nowhere under {READERS}: {unread}"


def test_every_knob_the_package_reads_is_documented():
    documented = _names(_knobs_section())
    read = _names_in_code("cometbft_tpu")
    assert len(read) > 50, "the census found too few names to be looking in the right place"
    missing = sorted(r for r in read if not any(_covers(d, r) for d in documented))
    assert not missing, f"read under cometbft_tpu/, absent from CLAUDE.md's Knobs section: {missing}"
