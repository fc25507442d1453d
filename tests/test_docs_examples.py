"""Executable documentation: every ```python block in README.md and
docs/*.md is extracted and run, so the docs cannot rot. (Shell blocks
are fenced ```bash and skipped.) Runs in CI via the normal tier-1
pytest invocation."""
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
DOC_FILES = sorted([ROOT / "README.md", *(ROOT / "docs").glob("*.md")])

_BLOCK_RE = re.compile(r"```python\n(.*?)```", re.DOTALL)


def _blocks():
    out = []
    for path in DOC_FILES:
        for i, m in enumerate(_BLOCK_RE.finditer(path.read_text())):
            out.append(pytest.param(
                path, m.group(1),
                id=f"{path.relative_to(ROOT)}#{i}"))
    return out


def test_docs_have_python_examples():
    """The docs subsystem ships runnable examples — at least one python
    block per documentation file set."""
    assert len(DOC_FILES) >= 4  # README + architecture/paper_mapping/benchmarks
    assert len(_blocks()) >= 4


@pytest.mark.parametrize("path,code", _blocks())
def test_docs_python_block_runs(path, code):
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    # run from the repo root so relative paths (BENCHMARK.json) resolve
    import os

    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        exec(compile(code, f"{path.name}:block", "exec"), {"__name__": "__docs__"})
    finally:
        os.chdir(cwd)
