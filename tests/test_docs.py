"""The documents describe the system as it is: every command they show
parses, every override names a field, every repository path exists and every
module resolves. ``ROADMAP.md`` and ``PERF.md`` are not here: a session that
runs no tests rewrites them.
"""

import dataclasses
import importlib.util
import os
import re
import shlex

import pytest

from deeplearning_cfn_tpu.cli.main import build_parser
from deeplearning_cfn_tpu.config import ExperimentConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "deeplearning_cfn_tpu")

README, ARCHITECTURE, OBSERVABILITY, OPERATIONS, SERVING, TRAINING = (
    "README.md", "docs/ARCHITECTURE.md", "docs/OBSERVABILITY.md",
    "docs/OPERATIONS.md", "docs/SERVING.md", "docs/TRAINING.md")


def _read(doc):
    with open(os.path.join(REPO_ROOT, doc)) as fh:
        return fh.read()


# -- commands ------------------------------------------------------------------

def _fenced_commands(text):
    """The ``dlcfn-tpu …`` commands of the fenced blocks, continuation
    lines joined, as ``(line number, argv after the program's name)``."""
    out, in_fence, pending = [], False, None
    for n, line in enumerate(text.splitlines(), 1):
        if line.strip().startswith("```"):
            in_fence, pending = not in_fence, None
            continue
        if not in_fence:
            continue
        if pending is not None:
            pending = (pending[0], pending[1] + " " + line.strip())
        elif re.match(r"\s*(\$ )?dlcfn-tpu\s", line):
            pending = (n, line.strip().lstrip("$ "))
        if pending is not None:
            if pending[1].endswith("\\"):
                pending = (pending[0], pending[1][:-1])
                continue
            out.append((pending[0],
                        shlex.split(pending[1], comments=True)[1:]))
            pending = None
    return out


_PLACEHOLDER = r"\.\.\.|…|<[^>]*>"


def _parses(parser, argv):
    """``...``, ``…`` and ``<x>`` stand for something the reader supplies:
    a value where one is due, and alone at the end any further arguments."""
    bare_dropped = [a for a in argv if not re.fullmatch(_PLACEHOLDER, a)]
    for candidate in (argv, bare_dropped):
        try:
            parser.parse_args([re.sub(_PLACEHOLDER, "x", a)
                               for a in candidate])
            return True
        except SystemExit:
            pass
    return False


@pytest.mark.parametrize(
    "doc", [README, OBSERVABILITY, OPERATIONS, SERVING, TRAINING])
def test_every_command_shown_parses(doc, capsys):
    commands = _fenced_commands(_read(doc))
    assert commands, f"{doc} shows no dlcfn-tpu command"
    parser = build_parser()
    refused = [f"{doc}:{n}: dlcfn-tpu {' '.join(argv)}"
               for n, argv in commands if not _parses(parser, argv)]
    capsys.readouterr()  # argparse's own usage text
    assert not refused, "\n".join(refused)


def test_command_check_reads_a_fenced_block(capsys):
    text = ("`dlcfn-tpu nonsense` outside a fence is prose\n```bash\n"
            "$ dlcfn-tpu train --preset <p> \\\n    train.steps=1 ...  # more\n"
            "dlcfn-tpu bench --no-such-flag 4\n```\n")
    commands = _fenced_commands(text)
    assert commands == [
        (3, ["train", "--preset", "<p>", "train.steps=1", "..."]),
        (5, ["bench", "--no-such-flag", "4"])]
    parser = build_parser()
    assert [_parses(parser, argv) for _, argv in commands] == [True, False]


# -- overrides -----------------------------------------------------------------

def _override_keys(text):
    sections = {f.name for f in dataclasses.fields(ExperimentConfig)}
    found = re.finditer(r"(?<![\w./-])([a-z_]+)((?:\.[a-z_0-9]+)+)=", text)
    return sorted({m.group(1) + m.group(2) for m in found
                   if m.group(1) in sections})


def _names_a_field(key):
    node = ExperimentConfig()
    for part in key.split("."):
        if isinstance(node, dict):
            return True  # free-form kwargs: the model's own business
        if not dataclasses.is_dataclass(node) or not hasattr(node, part):
            return False
        node = getattr(node, part)
    return True


@pytest.mark.parametrize("doc", [README, OBSERVABILITY, OPERATIONS, TRAINING])
def test_every_override_names_a_config_field(doc):
    keys = _override_keys(_read(doc))
    assert keys, f"{doc} shows no override"
    unknown = [k for k in keys if not _names_a_field(k)]
    assert not unknown, f"{doc}: no such field: {unknown}"


def test_override_check_tells_a_field_from_a_typo():
    assert _names_a_field("train.device_prefetch")
    assert _names_a_field("model.kwargs.anything")
    assert not _names_a_field("train.no_such_field")
    assert not _names_a_field("train.steps.deeper")
    assert _override_keys("`train.steps=1` runs/x.y=2 `nope.key=3`") == \
        ["train.steps"]


# -- paths ---------------------------------------------------------------------

_PATH = re.compile(r"^([\w.-]+(?:/[\w.-]+)*/?)(?::(\d+)(?:[-–]\d+)?)?$")
_SOURCE_DIRS = ("deeplearning_cfn_tpu", "tests", "tools", "benchmark", "docs")


def _tree_files():
    """Every source file of the tree by its bare name (``engine.py``)."""
    found = {}
    for top in _SOURCE_DIRS:
        for root, _, files in os.walk(os.path.join(REPO_ROOT, top)):
            for name in files:
                found.setdefault(name, os.path.join(root, name))
    return found


def _repository_paths(text):
    """Backticked tokens that name a file or directory of the repository,
    each with the line numbers cited: a path with a directory part that
    ends in a source suffix, or in a slash under a directory the repository
    has; or a source file's bare name. Run-time outputs (``trace.json``,
    ``logs/launch.jsonl``) and scopes (``optimizer/ema``) are neither."""
    dirs = set(os.listdir(REPO_ROOT)) | set(os.listdir(PACKAGE))
    out = {}
    for m in re.finditer(r"`([^`\n]+)`", text):
        hit = _PATH.match(m.group(1).strip())
        if not hit:
            continue
        path, line = hit.group(1), hit.group(2)
        if "/" not in path:
            named = re.search(r"\.(py|sh|md|toml)$", path)
        elif path.endswith("/"):
            named = path.split("/")[0] in dirs
        else:
            named = re.search(r"\.(py|sh|md|toml|cc|cpp|h|json|npz)$", path)
        if named:
            out.setdefault(path, set()).add(int(line) if line else 0)
    return out


def _resolve(path, tree_files):
    for base in (REPO_ROOT, PACKAGE):
        full = os.path.join(base, path)
        if os.path.exists(full):
            return full
    return tree_files.get(path)


@pytest.mark.parametrize(
    "doc", [README, ARCHITECTURE, OBSERVABILITY, OPERATIONS, SERVING,
            TRAINING])
def test_every_repository_path_exists(doc):
    paths = _repository_paths(_read(doc))
    assert paths, f"{doc} names no repository path"
    tree_files = _tree_files()
    missing = []
    for path, lines in sorted(paths.items()):
        full = _resolve(path, tree_files)
        if full is None:
            missing.append(path)
        elif max(lines):
            with open(full) as fh:
                length = sum(1 for _ in fh)
            if max(lines) > length:
                missing.append(f"{path}:{max(lines)} (file has {length})")
    assert not missing, f"{doc}: not in the repository: {missing}"


def test_path_check_tells_a_repository_path_from_an_output():
    paths = _repository_paths(
        "`train/trainer.py:70` `tests/` `nosuchfile.py` `logs/launch.jsonl` "
        "`optimizer/ema` `trace.json` `logs/` `<dir>/x.py` `gone/x.md`")
    assert paths == {"train/trainer.py": {70}, "tests/": {0},
                     "nosuchfile.py": {0}, "gone/x.md": {0}}
    tree_files = _tree_files()
    assert _resolve("train/trainer.py", tree_files).endswith("trainer.py")
    assert _resolve("engine.py", tree_files).endswith("serve/engine.py")
    assert _resolve("nosuchfile.py", tree_files) is None
    assert _resolve("gone/x.md", tree_files) is None


# -- modules -------------------------------------------------------------------

@pytest.mark.parametrize("doc", [README, OPERATIONS])
def test_every_module_shown_resolves(doc):
    modules = sorted(set(re.findall(
        r"python3? -m (deeplearning_cfn_tpu(?:\.\w+)+)", _read(doc))))
    assert modules, f"{doc} shows no python -m command"
    lost = [m for m in modules if importlib.util.find_spec(m) is None]
    assert not lost, f"{doc}: {lost}"
