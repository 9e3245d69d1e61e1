"""The documents and the tree agree: a path, a command or a measurement
tool that `README.md`, `PERF.md` or the verify skill names is in the
tree, and there is one benchmark command (`BENCHMARK.json`'s).

Exempt, because they are history and name what was true when written:
`CHANGES.md`, `ROADMAP.md`'s "Recent" section and `PERF.md` §6
(Findings). Only the working tree is read (a checkout of the committed
files need not be a git repository): hidden directories and
`chiprun_out/` are what running leaves behind and are skipped.
"""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PACKAGE = "pytorch_distributed_example_tpu"
# a back-ticked token that starts with one of these names a path
TOP_LEVEL = (
    f"{PACKAGE}/", "bench_matrix/", "benchmarks/", "tests/", "examples/",
    "bench.py", "chip_smoke.py",
)
DOCUMENTS = ("README.md", "PERF.md", ".claude/skills/verify/SKILL.md")


def _python_files():
    """The tree's own `*.py`, as paths relative to the root, outside
    `tests/` and `bench_matrix/` (the benchmark is not this PR's to
    describe) and outside what running leaves behind."""
    for top in sorted(ROOT.iterdir()):
        if top.name.startswith(".") or top.name in (
            "tests", "bench_matrix", "chiprun_out", "__pycache__",
        ):
            continue
        files = [top] if top.is_file() else top.rglob("*.py")
        for f in files:
            if f.suffix == ".py":
                yield f.relative_to(ROOT)


def _current_text(document: str) -> str:
    text = (ROOT / document).read_text()
    if document == "PERF.md":  # §6 is the record of earlier PRs
        text = re.sub(r"(?ms)^## 6\. .*?(?=^## 7\. )", "", text)
        assert "## 7. " in text and "## 6. " not in text
    return text


def _path_of(token: str):
    """(path, names after `::`) for a token that names a path, else
    None: a placeholder (`<cell>`), a glob or a brace list names none."""
    head = token.split()[0].rstrip(".,;:")
    if not head.startswith(TOP_LEVEL) or re.search(r"[<>*{}$]|\.\.\.|…", head):
        return None
    path, _, names = head.partition("::")
    path = re.sub(r":\d+(-\d+)?$", "", path)  # file.py:274
    return path, [re.sub(r"\[.*$", "", n) for n in names.split("::") if n]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_live_paths(document):
    missing = []
    seen = 0
    for token in re.findall(r"`([^`\n]+)`", _current_text(document)):
        named = _path_of(token)
        if named is None:
            continue
        seen += 1
        path, names = named
        target = ROOT / path
        if not target.exists():
            missing.append(token)
        elif target.suffix == ".py":
            source = target.read_text()
            missing += [
                token for n in names if not re.search(rf"\b{re.escape(n)}\b", source)
            ]
    assert seen, f"{document}: no path token found (the pattern is stale)"
    assert not missing, f"{document} names what is not in the tree: {missing}"


def _module_file(module: str):
    base = ROOT / Path(*module.split("."))
    for cand in (base.with_suffix(".py"), base / "__main__.py", base / "__init__.py"):
        if cand.exists():
            return cand
    return None


def _fenced_blocks(text: str):
    """The bodies of a markdown text's fenced blocks, whatever their
    language: a fence line opens a block and the next one closes it."""
    block = None
    for line in text.splitlines(keepends=True):
        if line.startswith("```"):
            if block is not None:
                yield "".join(block)
            block = None if block is not None else []
        elif block is not None:
            block.append(line)


def test_readme_commands_name_live_targets():
    """Each `python <file>` / `python -m <module>` of the README's fenced
    blocks, and every `*.py` argument of such a command, resolves."""
    missing, seen = [], 0
    for block in _fenced_blocks((ROOT / "README.md").read_text()):
        for line in block.replace("\\\n", " ").splitlines():
            words = line.split("#")[0].split()
            starts = [i for i, w in enumerate(words) if re.fullmatch(r"python3?", w)]
            if not starts:
                continue
            args = words[starts[0] + 1:]
            if args[:2] == ["-m", "pytest"] or args[:1] == ["-c"]:
                continue
            seen += 1
            if args[:1] == ["-m"]:
                if _module_file(args[1]) is None:
                    missing.append(line.strip())
                args = args[2:]
            missing += [
                line.strip() for a in args
                if a.endswith(".py") and not (ROOT / a).exists()
            ]
    assert seen >= 5, "the README's commands were not found (the pattern is stale)"
    assert not missing, missing


def test_no_bench_options():
    """The 12 `BENCH_*` environment options went with `bench.py`; the
    benchmark takes its four flags and reads no such name."""
    hits = [
        str(f) for f in _python_files()
        if re.search(r"BENCH_[A-Z]", (ROOT / f).read_text())
    ]
    assert not hits, hits


def test_one_benchmark_command():
    command = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    assert command[:2] == ["python3", "-m"]
    assert _module_file(command[2]) is not None, command
    assert not (ROOT / "bench.py").exists()
    assert not (ROOT / "benchmarks").exists()


def test_code_names_no_deleted_tool():
    """No comment, docstring or string of the program points a reader at
    the measurement scripts that are gone."""
    hits, walked = {}, 0
    for f in _python_files():
        walked += 1
        found = re.findall(r"benchmarks/|\w*bench\.py", (ROOT / f).read_text())
        if found:
            hits[str(f)] = sorted(set(found))
    assert not hits, hits
    assert walked > 100  # the walk reached the package
