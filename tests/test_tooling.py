"""Tier-1 tree hygiene + tooling smoke: scripts/check_tree.sh (no
tracked bytecode, src compiles, docs exist with resolving file refs),
the README quickstart executed verbatim and the tool-calling agent-loop
example, so docs and examples can't silently bit-rot."""
import os
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(ROOT / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


def test_check_tree():
    subprocess.run(["bash", str(ROOT / "scripts" / "check_tree.sh")],
                   check=True, cwd=ROOT, timeout=300)


def test_lint_gate_clean_and_corpus_bites():
    """The static-analysis gate (part of check_tree) holds both ways:
    the shipped serving core is clean under the committed baseline, and
    the analyzer is not trivially silent — pointed at its self-test
    corpus it reports findings and exits non-zero.  Exact per-line
    corpus expectations live in tests/test_lint.py."""
    gate = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--baseline"],
        cwd=ROOT, env=_env(), timeout=60, capture_output=True, text=True)
    assert gate.returncode == 0, gate.stdout + gate.stderr
    corpus = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint",
         str(ROOT / "tests" / "lint_corpus")],
        cwd=ROOT, env=_env(), timeout=60, capture_output=True, text=True)
    assert corpus.returncode == 1, corpus.stdout + corpus.stderr
    assert "findings" in corpus.stderr     # the summary line
    assert "donate-no-rebind" in corpus.stdout


def test_readme_quickstart_executes():
    """The README's first python code block IS the quickstart — run it
    verbatim so the documented example can never rot.  It must print
    generated content and exit cleanly."""
    readme = (ROOT / "README.md").read_text()
    blocks = re.findall(r"```python\n(.*?)```", readme, re.DOTALL)
    assert blocks, "README.md has no ```python quickstart block"
    out = subprocess.run([sys.executable, "-c", blocks[0]],
                         check=True, cwd=ROOT, env=_env(), timeout=580,
                         capture_output=True, text=True).stdout
    assert "prefix-cached prompt tokens:" in out, out


def test_tool_calling_example_smoke():
    subprocess.run([sys.executable,
                    str(ROOT / "examples" / "tool_calling.py")],
                   check=True, cwd=ROOT, env=_env(), timeout=580)
