"""The documents name files that exist.

Every ``python`` / ``python3`` command line and every back-quoted
``*.py`` / ``*.md`` / ``*.json`` path in ``README.md``, ``docs/*.md``,
``PERF.md`` and the verify skill must resolve in the tree: a document that
tells a newcomer to run a script that is gone sends them to the wrong
yardstick.  A path may be written from the repo root, from the package
(``serving/engine.py``), or as a bare file name (``chip_smoke.py``).
"""

import os
import pathlib
import re
import shlex

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = "distributed_tensorflow_ibm_mnist_tpu"
DOCS = ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md",
        *sorted(str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md"))]
# what a run leaves behind, or a second copy of a tree, is not the tree
SKIP_DIRS = {".git", ".cache", "chiprun_out", "__pycache__", "build",
             ".pytest_cache"}
# a pattern, a placeholder or a path outside the repo names no one file
NOT_A_PATH = re.compile(r"[<>*{}$%]|^/|^~|^NN_|://")
QUOTED = re.compile(r"`([^`\s]+?\.(?:py|md|json))(?:::[^`]*|\s[^`]*)?`")
COMMAND = re.compile(r"\bpython3?\s+([^`|;&#\n]+)")


def _tree():
    files = set()
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        rel = pathlib.Path(root).relative_to(REPO)
        files.update((rel / n).as_posix() for n in names)
    return files


def _resolves(path, files):
    path = path.removeprefix("./")
    return (path in files or (REPO / path).is_dir()
            or any(f.endswith("/" + path) for f in files))


def _command_targets(args):
    """The files a ``python ...`` command line needs: its script, the
    module behind ``-m``, and for pytest the paths it collects."""
    try:
        words = shlex.split(args)
    except ValueError:
        words = args.split()
    if not words or words[0] == "-c":
        return []
    if words[0] == "-m" and len(words) > 1:
        if words[1] == "pytest":
            return [w.split("::")[0] for w in words[2:]
                    if "/" in w and not w.startswith("-")]
        return [words[1].replace(".", "/")]
    return [words[0]] if words[0].endswith(".py") else []


def test_documents_name_files_that_exist():
    files = _tree()
    missing = []
    for doc in DOCS:
        text = (REPO / doc).read_text()
        named = [m.group(1) for m in QUOTED.finditer(text)]
        for m in COMMAND.finditer(text):
            named += _command_targets(m.group(1))
        for path in named:
            if NOT_A_PATH.search(path):
                continue
            if not (_resolves(path, files) or _resolves(path + ".py", files)):
                missing.append(f"{doc}: {path}")
    assert not missing, "\n".join(sorted(set(missing)))
