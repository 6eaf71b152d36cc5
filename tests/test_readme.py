"""README.md's claims are the tree's: the flags it names are flags of the
entry point it names them for, the paths it names exist, and the sections of
PERF.md it points at are sections PERF.md has. A document nobody runs goes
stale in silence; these are the parts of it a test can run."""

import importlib
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(name: str) -> str:
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


def _commands(text: str, entry: str) -> list[str]:
    """Every stretch of the README that begins at ``<entry>.py`` and runs to
    the end of its command: the rest of a code block's line (continuation
    lines joined) or of an inline code span."""
    text = re.sub(r"\\\n\s*", " ", text)
    return re.findall(rf"(?<![\w/]){entry}\.py\b([^`\n]*)", text)


@pytest.mark.parametrize("entry", ["train", "predict", "serve", "fleet"])
def test_flags_named_for_an_entry_point_are_its_flags(entry):
    commands = _commands(_read("README.md"), entry)
    assert commands, f"README.md never shows {entry}.py"
    parser = importlib.import_module(entry).build_parser()
    known = {s for a in parser._actions for s in a.option_strings}
    named = {flag for c in commands
             for flag in re.findall(r"(?<![\w-])(--?[a-z][\w-]*)", c)}
    assert named, f"README.md shows {entry}.py with no flag at all"
    assert named <= known, (
        f"README.md names flags {entry}.py does not take: "
        f"{sorted(named - known)}")


def test_paths_the_readme_names_exist():
    named = set(re.findall(
        r"(?<![\w./-])((?:cgnn_tpu|scripts|benchmark|tests)/[\w./{},-]*\w)",
        _read("README.md")))
    assert len(named) >= 20, sorted(named)
    missing = []
    for path in sorted(named):
        # ``train/{step,force_step}.py`` names each member
        m = re.search(r"\{([^}]*)\}", path)
        members = ([path[:m.start()] + x + path[m.end():]
                    for x in m.group(1).split(",")] if m else [path])
        missing += [p for p in members
                    if not os.path.exists(os.path.join(ROOT, p))]
    assert not missing, f"README.md names paths that do not exist: {missing}"


def test_perf_sections_the_readme_points_at_are_headings():
    pointed = set(re.findall(r"PERF\.md\s+§(\w+)", _read("README.md")))
    assert pointed, "README.md points at no section of PERF.md"
    headings = set(re.findall(r"^## (\w+)\.", _read("PERF.md"), re.M))
    assert pointed <= headings, (
        f"README.md points at PERF.md §{sorted(pointed - headings)}; "
        f"PERF.md has §{sorted(headings)}")
