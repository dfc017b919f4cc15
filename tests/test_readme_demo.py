"""The README's five-minute demo must actually run — docs are the product
surface (the reference's README WAS its API; SURVEY.md §3.1), so the demo
commands are executed verbatim from the file. If someone edits the README
without updating the CLI (or vice versa), this fails.
"""

import os
import re
import shlex

import pytest

from deeplearning_cfn_tpu.cli.main import main

README = os.path.join(os.path.dirname(__file__), "..", "README.md")


def _bash_blocks():
    text = open(README).read()
    return re.findall(r"```bash\n(.*?)```", text, re.DOTALL)


def _commands(block):
    """Join continuation lines, drop comments, keep dlcfn-tpu commands."""
    joined = block.replace("\\\n", " ")
    cmds = []
    for line in joined.splitlines():
        line = line.strip()
        if line.startswith("dlcfn-tpu "):
            cmds.append(shlex.split(line.split("#")[0])[1:])
    return cmds


def test_readme_five_minute_demo(tmp_path, capsys, monkeypatch):
    blocks = _bash_blocks()
    assert blocks, "README lost its bash blocks"
    demo_cmds = [c for b in blocks[:3] for c in _commands(b)]
    # Expect at least: doctor, first train, resume train, ckpt list/rollback.
    assert any(c[0] == "doctor" for c in demo_cmds), demo_cmds
    trains = [c for c in demo_cmds if c[0] == "train"]
    assert len(trains) >= 2, "README demo should train then resume"

    # Shrink the documented step counts but KEEP them distinct (30→4,
    # 60→8): the resume leg must really train 4 more steps (not restore
    # and no-op), and the two committed checkpoints {4, 8} give the
    # rollback command something real to delete.
    step_map = {}

    def relocate(cmd):
        # Point the documented /tmp/demo paths into the test's tmp dir and
        # shrink the step counts (the commands stay otherwise verbatim).
        out = []
        for a in cmd:
            a = a.replace("/tmp/demo", str(tmp_path))

            def shrink(m):
                orig = int(m.group(0).split("=")[1])
                step_map.setdefault(orig, 4 * (len(step_map) + 1))
                return f"train.steps={step_map[orig]}"

            a = re.sub(r"train\.steps=\d+", shrink, a)
            out.append(a)
        return out

    ran = 0
    for cmd in demo_cmds:
        if cmd[0] == "doctor":
            assert main(["doctor"]) == 0
            ran += 1
        elif cmd[0] == "train":
            assert main(relocate(cmd)) == 0, cmd
            ran += 1
        elif cmd[0] == "ckpt":
            args = relocate(cmd)
            if args[1] == "rollback" and "--step" in args:
                # The documented rollback step may exceed the shrunk runs'
                # steps; roll back to the earliest committed step instead
                # (authoritative list, not a dir glob — COMMIT markers
                # define "committed").
                from deeplearning_cfn_tpu.ckpt import committed_steps

                steps = committed_steps(args[2])
                assert len(steps) >= 2, \
                    f"demo should have left >=2 checkpoints, got {steps}"
                args[args.index("--step") + 1] = str(steps[0])
            assert main(args) == 0, args
            ran += 1
    assert ran >= 4, f"only ran {ran} demo commands: {demo_cmds}"
    out = capsys.readouterr().out
    assert "resumed from step" in out, \
        "the README's resume claim did not reproduce"
    # The resume leg genuinely trained past the first run's endpoint.
    assert re.search(r'"step": 8', out) or "step': 8" in out, \
        "resume leg did not reach step 8"
