"""The documents send a reader only to scripts that exist and to flags
those scripts take.

`README.md` pointed at a harness the ledger never read for five PRs; this
is the test that would have said so.  For each document: (scripts) every
`python <path>.py` a fenced block or a back-ticked span names is a file of
the tree; (flags) every `--flag` shown after `chip_smoke.py` or
`benchmark/run.py` is one that script's `--help` lists.
"""

import functools
import pathlib
import re
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCS = ("README.md", ".claude/skills/verify/SKILL.md", "benchmark/README.md")
FLAG_CHECKED = ("chip_smoke.py", "benchmark/run.py")

_FENCE = re.compile(r"```.*?```", re.S)
_SPAN = re.compile(r"`[^`]+`")
_SCRIPT = re.compile(r"(?:/root/repo/)?([\w./-]+\.py)$")


def _code_tokens(paragraph: str) -> list[str]:
    """The words of a paragraph's fenced blocks and back-ticked spans, in
    reading order."""
    regions = [(m.start(), m.group(0)) for m in _FENCE.finditer(paragraph)]
    prose = _FENCE.sub(lambda m: " " * len(m.group(0)), paragraph)
    regions += [(m.start(), m.group(0)) for m in _SPAN.finditer(prose)]
    return [
        tok for _, text in sorted(regions) for tok in text.strip("`").split()
    ]


def _paragraphs(doc: str) -> list[str]:
    text = (REPO / doc).read_text(encoding="utf-8")
    # a fenced block is one paragraph whatever blank lines it holds
    held = _FENCE.sub(lambda m: m.group(0).replace("\n\n", "\n"), text)
    return re.split(r"\n\s*\n", held)


def _commands(doc: str) -> list[tuple[str, bool, list[str]]]:
    """(script, run by `python`, flags shown after it) for every script a
    paragraph of the document names in code.  `python` opens a new command,
    so the flags of `python -m pytest ...` belong to no script."""
    found = []
    for paragraph in _paragraphs(doc):
        flags = None  # of the script named last, while one is
        after_python = False
        for tok in _code_tokens(paragraph):
            m = _SCRIPT.match(tok)
            if re.fullmatch(r"python3?", tok):
                flags, after_python = None, True
            elif m and not tok.startswith(("/tmp/", "$")):
                flags = []
                found.append((m.group(1), after_python, flags))
                after_python = False
            elif flags is not None and re.match(r"--[a-z]", tok):
                flags.append(tok.split("=")[0].rstrip(".,;:)"))
            elif not tok.startswith("-"):
                after_python = False  # `python -m module ...`
    return found


@functools.lru_cache(maxsize=None)
def _help_flags(script: str) -> frozenset:
    out = subprocess.run(
        [sys.executable, str(REPO / script), "--help"],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return frozenset(re.findall(r"--[a-z][\w-]*", out.stdout))


def _missing_scripts(commands):
    return sorted({
        script for script, run_by_python, _ in commands
        if run_by_python and not (REPO / script).is_file()
    })


def _unknown_flags(commands):
    return sorted({
        (script, flag)
        for script, _, flags in commands if script in FLAG_CHECKED
        for flag in flags if flag not in _help_flags(script)
    })


@pytest.mark.parametrize("doc", DOCS)
@pytest.mark.parametrize("check", [_missing_scripts, _unknown_flags])
def test_docs_name_what_the_tree_has(doc, check):
    commands = _commands(doc)
    assert commands, f"{doc} names no script: the scan is blind"
    assert check(commands) == []
