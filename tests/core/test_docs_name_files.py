"""A document that names a file names one that exists.

Every word in backticks (or in a fenced block) that ends in ``.py`` and
either starts with a top-level directory or file of the repository or is
what a ``python`` command runs is looked up from the root, a trailing
``:line`` or ``::test`` cut off, a ``*`` taken as a glob. A PR that deletes
a script mends the documents that send a reader to it, or fails here.
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DOCUMENTS = sorted(
    str(p.relative_to(REPO)) for p in (
        REPO / "README.md", REPO / "benchmarks" / "README.md",
        REPO / ".claude" / "skills" / "verify" / "SKILL.md",
        *(REPO / "docs").glob("*.md"),
    ))
# a run of backticks, the code, the same run again: inline or fenced
CODE = re.compile(r"(`+)(.+?)\1(?!`)", re.S)


def named_files(text):
    top = {p.name for p in REPO.iterdir()}
    for _, code in CODE.findall(text):
        command = ""
        for raw in code.split():
            word = raw.split(":")[0].strip("\"'(),;.")
            if (word.endswith(".py") and not set(word) & set("<>{}$")
                    and (word.split("/")[0] in top
                         or command in ("python", "python3"))):
                yield word
            command = raw


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_python_file_a_document_names_exists(document):
    words = sorted(set(named_files((REPO / document).read_text())))
    assert not [w for w in words if not any(REPO.glob(w))]


def test_the_rule_finds_a_path_behind_a_command_and_before_a_line_number():
    text = ("run `python benchmarks/gone.py --smoke`, see `tests/core/x.py:12`,\n"
            "`serve/engine.py` and `benchmark/readers/<name>.py`\n"
            "```\npython bench_gone.py\npython chip_smoke.py\n```\n")
    assert list(named_files(text)) == [
        "benchmarks/gone.py", "tests/core/x.py", "bench_gone.py", "chip_smoke.py"]
