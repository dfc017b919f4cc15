"""Device time by section of the program (``harness/sections.py``) and the
program's host spans read from the trace file (``harness/program_spans.py``):
the pattern table on hand-written ``op_name``s, the sums on the trace
recorded on a v5e chip, the check that the compiled text is the program the
trace shows, and the tiny cell end to end on the CPU."""

import json
import os
import sys

import pytest

from benchmark_tiny_tree import REPO, build, env

sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import program_spans, sections, xplane  # noqa: E402

FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "small.xplane.pb")
NEW_DEVICE = ["head_loss_ms", "dropout_ms", "blocks_ms", "optimizer_ms",
              "unscoped_share"]
NEW_SPAN = ["input_stall_ms", "dispatch_ms"]
M = "jit(train_step)/jvp(TransformerCausalLm)"
T = "jit(train_step)/transpose(jvp(TransformerCausalLm))"


@pytest.mark.parametrize("op_name,section", [
    (f"{M}/layer_3/mlp/Dropout_0/jit(_bernoulli)/jit(_uniform)/xor",
     "dropout"),
    (f"{T}/layer_3/self_attn/Dropout_0/div", "dropout"),
    (f"{M}/TransformerCausalLm._embed/dropout/jit(_bernoulli)/lt",
     "dropout"),
    ("jit(train_step)/step_rng/jit(_threefry_fold_in)/slice", "dropout"),
    (f"{M}/layer_0/self_attn/self_attn.core_attention/flash_fwd/pallas_call",
     "flash"),
    (f"{T}/layer_0/self_attn/self_attn.core_attention/flash_bwd_dq/"
     f"pallas_call", "flash"),
    (f"{M}/lm_head/token.attend/dot_general", "head"),
    (f"{T}/lm_head/token.attend/dot_general", "head"),
    ("jit(train_step)/jvp(lm_loss)/reduce_sum", "loss"),
    ("jit(train_step)/transpose(jvp(lm_loss))/jit(take_along_axis)/"
     "scatter-add", "loss"),
    ("jit(train_step)/optimizer/jit(clip)/max", "optimizer"),
    ("jit(train_step)/optimizer/ema/mul", "optimizer"),
    (f"{M}/layer_11/self_attn/query/dot_general", "attn_proj"),
    # Beside the kernels, not one of them: the rest of self_attn.
    (f"{T}/layer_11/self_attn/transpose;{T}/layer_11/self_attn/"
     f"self_attn.core_attention/transpose", "attn_proj"),
    (f"{M}/layer_0/self_attn/self_attn.core_attention/bhqk,bhkd->bhqd/"
     f"dot_general", "attn_proj"),
    (f"{M}/layer_2/mlp/mlp_in/dot_general", "mlp"),
    (f"{M}/layer_2/mlp_norm/rsqrt", "norm"),
    (f"{M}/layer_2/self_attn_norm/mul", "norm"),
    (f"{M}/final_norm/div", "norm"),
    (f"{M}/TransformerCausalLm._embed/embed_norm/mul", "norm"),
    (f"{M}/TransformerCausalLm._embed/token/jit(_take)/gather", "embed"),
    (f"{T}/TransformerCausalLm._embed/token/jit(_take)/scatter-add",
     "embed"),
    ("jit(train_step)/mul", "unscoped"),
    ("jit(train_step)/jvp()/slice", "unscoped"),
    ("", "unscoped"),
])
def test_an_op_name_falls_in_exactly_one_section(op_name, section):
    assert sections.classify(op_name) == section


def test_a_fusion_that_holds_a_mask_is_dropouts_whatever_its_root():
    root = f"{M}/layer_0/mlp/mlp_out/add"
    mask = f"{M}/layer_0/mlp/Dropout_0/jit(_bernoulli)/lt"
    assert sections.classify(root, ["", root]) == "mlp"
    assert sections.classify(root, ["", mask, root]) == "dropout"
    assert sections.classify(
        f"{M}/lm_head/token.attend/dot_general",
        ["jit(train_step)/step_rng/jit(_threefry_fold_in)/slice"]) \
        == "dropout"


def test_the_table_names_the_ten_sections_in_order_and_ends_in_a_catch_all():
    assert [name for name, _ in sections.SECTIONS] == [
        "dropout", "flash", "head", "loss", "optimizer", "attn_proj", "mlp",
        "norm", "embed", "unscoped"]
    assert sections.SECTIONS[-1][1] == ""


HLO = """HloModule jit_train_step, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %multiply.9 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(train_step)/optimizer/mul" source_file="x.py"}
}

ENTRY %main.1 (state_step.1: s32[], batch: f32[4]) -> f32[4] {
  %batch = f32[4]{0} parameter(1), metadata={op_name="batch['tokens']"}
  %state_step.1 = s32[] parameter(0), metadata={op_name="state.step"}
  %constant.3 = f32[] constant(2)
  %fusion.7 = f32[4]{0} fusion(%batch), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/optimizer/mul" source_file="x.py" source_line=3}
  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%fusion.7)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  ROOT %flash_fwd.12 = f32[4]{0} custom-call(%copy-done.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TransformerCausalLm)/layer_0/self_attn/self_attn.core_attention/flash_fwd/pallas_call"}
}
"""


def test_instruction_scopes_of_a_compiled_text():
    scopes = sections.instruction_scopes(HLO)
    assert scopes["entry"] == {
        "fusion.7": "jit(train_step)/optimizer/mul",
        "copy-start.2": "", "copy-done.2": "",
        "flash_fwd.12": "jit(train_step)/jvp(TransformerCausalLm)/layer_0/"
                        "self_attn/self_attn.core_attention/flash_fwd/"
                        "pallas_call"}
    # Every computation's instructions, by their module-wide names, and
    # what each fusion holds.
    assert scopes["all"]["multiply.9"] == "jit(train_step)/optimizer/mul"
    assert scopes["inside"] == {
        "fusion.7": ["", "jit(train_step)/optimizer/mul"]}
    # What a trace's ``labels`` has for the same instructions.
    assert scopes["labels"]["fusion.7"] == "fusion.7 f32[4]"
    assert scopes["labels"]["copy-start.2"] == "copy-start.2 (f32[4]"
    assert scopes["labels"]["flash_fwd.12"] == xplane.label(
        "%flash_fwd.12 = f32[4]{0} custom-call(%copy-done.2)")
    assert sections.sections_of(scopes)["fusion.7"] == "optimizer"
    assert set(scopes["entry"]) < set(scopes["all"])
    assert [c["name"] for c in xplane.pallas_calls(HLO)] == ["flash_fwd.12"]


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(FIXTURE, ("window",))


def test_sections_sum_to_the_busy_time_of_the_recorded_trace(trace):
    window = trace.window("window")
    ops = trace.op_seconds(0, window)
    # A hand-made map: the kernels under their scopes, the copies with no
    # metadata (as the chip's compiler leaves them), and two operations the
    # text does not have at all.
    core = "layer_0/self_attn/self_attn.core_attention"
    names = {"jvp__.1": f"{M}/{core}/flash_fwd/pallas_call",
             "transpose_jvp___.2": f"{T}/{core}/flash_bwd_dkdv/pallas_call",
             "transpose_jvp___.3": f"{T}/{core}/flash_bwd_dq/pallas_call",
             "slice_reduce_fusion": "jit(train_step)/jvp(lm_loss)/reduce_sum",
             "convert_reduce_fusion": "jit(train_step)/optimizer/add",
             "broadcast_in_dim.11": f"{M}/layer_0/mlp/mlp_in/add",
             "broadcast_in_dim.15": f"{M}/layer_0/mlp/Dropout_0/mul"}
    absent = {"copy.21", "copy.22"}
    section_of = {n: sections.classify(names.get(n, ""))
                  for n in ops if n not in absent}
    seconds, missing = sections.by_section(ops, section_of)
    assert sorted(missing) == sorted(absent)
    assert list(seconds) == [name for name, _ in sections.SECTIONS]
    assert sum(seconds.values()) == pytest.approx(trace.busy_s(window),
                                                  rel=1e-9)
    assert seconds["flash"] == pytest.approx(0.000365145, rel=1e-6)
    assert seconds["loss"] == pytest.approx(6.483e-06)
    assert seconds["optimizer"] == pytest.approx(3.274e-06)
    assert seconds["mlp"] == pytest.approx(8.418e-06)
    assert seconds["dropout"] == pytest.approx(8.417e-06)
    assert seconds["head"] == seconds["embed"] == 0.0
    rest = sum(v for n, v in ops.items() if n not in names)
    assert seconds["unscoped"] == pytest.approx(rest)


class _Say:
    def __init__(self):
        self.lines = []

    def __call__(self, text):
        self.lines.append(text)


def _text_of(path, op_names=None, edit=lambda text: text):
    """A compiled text made of a recorded trace's own events (an event's
    name is its instruction's text), each with the ``op_name`` given for
    it."""
    from jax.profiler import ProfileData

    lines, seen = [], set()
    for plane in ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                name = xplane.short_name(ev.name)
                if name in seen:
                    continue
                seen.add(name)
                op_name = (op_names or {}).get(name)
                lines.append("  " + edit(ev.name) + (
                    f', metadata={{op_name="{op_name}"}}' if op_name else ""))
    return "\n".join(["HloModule jit_step", "",
                      "ENTRY %main.1 (x: f32[4]) -> f32[4] {"]
                     + lines + ["}", ""])


class _Compiled:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def _ctx(trace, say):
    return {"trace": trace, "window": trace.window("window"), "say": say,
            "cell": None, "device": {"count": 1},
            "run": {"steps": 3, "pallas_calls": [{"name": "jvp__.1"}]}}


MASKED = """
%fused_computation.77 (p: f32[4]) -> f32[4] {
  %p.77 = f32[4]{0} parameter(0)
  %lt.77 = pred[4]{0} compare(%p.77, %p.77), direction=LT, metadata={op_name="jit(train_step)/jvp(TransformerCausalLm)/layer_0/mlp/Dropout_0/jit(_bernoulli)/lt"}
  ROOT %add.77 = f32[4]{0} add(%p.77, %p.77), metadata={op_name="jit(train_step)/jvp(TransformerCausalLm)/layer_0/mlp/mlp_out/add"}
}
"""


def test_the_reading_says_how_it_closes_and_where_the_masks_are(
        trace, monkeypatch):
    """``read`` on the recorded trace with a text made of the trace's own
    instructions: every operation is found, the metrics and the kernels
    together are the busy time, and the fusion that holds a ``Dropout``
    instruction under an ``mlp`` root is dropout's and said to be."""
    core = f"{M}/layer_0/self_attn/self_attn.core_attention"
    text = _text_of(FIXTURE, {
        "jvp__.1": f"{core}/flash_fwd/pallas_call",
        "slice_reduce_fusion": f"{M}/layer_0/mlp/mlp_out/add",
        "convert_reduce_fusion": f"{M}/lm_head/token.attend/dot_general"})
    # The fusion's body, as the chip's compiler prints it beside the entry.
    text = text.replace("\nENTRY", MASKED + "\nENTRY").replace(
        "calls=%fused_computation.1", "calls=%fused_computation.77")
    monkeypatch.setattr(sections, "compile_step",
                        lambda cell, devices: _Compiled(text))
    say = _Say()
    ctx = _ctx(trace, say)
    seconds = sections.read(ctx)
    assert sections.read(ctx) is seconds        # one compile for all readers
    assert seconds["flash"] == pytest.approx(0.000100778)
    assert seconds["dropout"] == pytest.approx(6.483e-06)
    assert seconds["mlp"] == 0.0
    assert seconds["head"] == pytest.approx(3.274e-06)
    assert sum(seconds.values()) == pytest.approx(
        trace.busy_s(ctx["window"]), rel=1e-9)
    said = "\n".join(say.lines)
    assert "24 of the trace's 24 operations are instructions of its text" \
        in said
    assert "not in the text" not in said
    assert "busy (+0.00 %)" in said
    assert "dropout by the section of each operation's root, ms a step: " \
           "mlp 0.00 (" in said
    assert "longest unscoped operations" in said
    assert sections.ms_per_step(ctx, "head", "loss") == pytest.approx(
        1e3 * 3.274e-06 / 3)


def test_a_little_of_another_program_is_counted_unscoped_and_named(
        trace, monkeypatch):
    """An operation of the window that the text lacks, or has with another
    result type (the window's marker program), is named and counted
    unscoped, never dropped."""
    head = f"{M}/lm_head/token.attend/dot_general"
    ops = trace.op_seconds(0, trace.window("window"))
    small = ["slice-done", "slice-done.3"]      # 0.11 % of the time
    text = _text_of(FIXTURE, {n: head for n in ops})
    text = "\n".join(
        line for line in text.splitlines()
        if not line.startswith(f"  %{small[0]} = ")).replace(
            f"%{small[1]} = bf16[", f"%{small[1]} = f32[")
    monkeypatch.setattr(sections, "compile_step",
                        lambda cell, devices: _Compiled(text))
    say = _Say()
    ctx = _ctx(trace, say)
    seconds = sections.read(ctx)
    assert seconds["unscoped"] == pytest.approx(ops[small[0]] + ops[small[1]])
    assert seconds["head"] == pytest.approx(
        sum(ops.values()) - seconds["unscoped"])
    said = "\n".join(say.lines)
    assert "22 of the trace's 24 operations are instructions" in said
    assert "not in the text, 0.110 % of the operations' time" in said
    assert all(trace.labels[n] in said for n in small)


@pytest.mark.parametrize("edit", [
    # The same names with other result types: a variant of the step.
    lambda text: text.replace("bf16[2,4,", "bf16[4,4,"),
    # Other numbering: the names of the long operations are not there.
    lambda text: text.replace("jvp__", "jvp__x").replace(
        "transpose_jvp___", "transpose_jvp___x"),
])
def test_a_text_of_another_program_reads_nothing(trace, monkeypatch, edit):
    """The join is by name, and names like ``fusion.22`` exist in any
    variant of the step: where the text's instructions are not the trace's
    by name and result type, no section metric is reported."""
    text = _text_of(FIXTURE, edit=edit)
    monkeypatch.setattr(sections, "compile_step",
                        lambda cell, devices: _Compiled(text))
    say = _Say()
    ctx = _ctx(trace, say)
    assert sections.read(ctx) is None
    assert ctx["sections"] is None              # and is not tried again
    assert sections.ms_per_step(ctx, "head", "loss") is None
    assert "the text is not the program the window ran" in say.lines[-1]


def test_readers_find_nothing_without_a_trace():
    ctx = {"trace": None, "window": None, "run": {"steps": 3}, "say": _Say()}
    assert sections.read(ctx) is None
    assert sections.ms_per_step(ctx, "head", "loss") is None
    assert "sections" not in ctx


def test_readers_share_one_reading(trace):
    """What ``read`` keeps in ``ctx`` is what every reader divides."""
    ctx = {"trace": trace, "window": trace.window("window"),
           "run": {"steps": 3}, "say": _Say(),
           "sections": {name: 0.003 for name, _ in sections.SECTIONS}}
    assert sections.ms_per_step(ctx, "head", "loss") == pytest.approx(2.0)
    assert sections.ms_per_step(ctx, "attn_proj", "mlp", "norm",
                                "embed") == pytest.approx(4.0)


def test_idle_time_by_program_span_on_the_recorded_trace(trace):
    window = trace.window("window")
    # The program's spans where the fixture has the benchmark's wrappers
    # (same instants): the sleeps under train.next_batch, the rest hooks.
    recorded = xplane.Trace.from_file(
        FIXTURE, ("fit hook", "next(batch)")).spans
    spans = [("train.hooks" if n == "fit hook" else "train.next_batch", s, e)
             for n, s, e in recorded]
    say = _Say()
    ctx = {"trace": trace, "window": window, "run": {"steps": 3}, "say": say}
    gaps = program_spans.idle_by_program_span(ctx, spans)
    assert [g[0] for g in gaps] == ["train.next_batch", "train.hooks"]
    assert gaps[0][1] == pytest.approx(0.065, abs=0.002)
    assert "device 0 idle by program span" in say.lines[-1]
    assert "(no span)" not in say.lines[-1]
    # The spans lie end to end over the window: next to nothing is bare.
    bare = float(say.lines[-1].rsplit("; ", 1)[1].split(" ")[0])
    assert 0 <= bare < 0.02
    # With the hooks' spans gone, their gaps still go to a span that
    # touches them, and the line says how much no span covers.
    say2 = _Say()
    program_spans.idle_by_program_span(
        dict(ctx, say=say2), [s for s in spans if s[0] != "train.hooks"])
    assert float(say2.lines[-1].rsplit("; ", 1)[1].split(" ")[0]) > bare


def test_a_trace_of_a_program_without_spans_yields_none():
    """The parent of PR 24 annotates nothing: the recorded trace has the
    benchmark's wrappers and no ``train.*`` event."""
    assert program_spans.host_spans(FIXTURE) == []
    assert program_spans.PROGRAM_SPAN.match("train.next_batch")
    assert program_spans.PROGRAM_SPAN.match("ckpt.save")
    assert not program_spans.PROGRAM_SPAN.match("fit hook")
    assert not program_spans.PROGRAM_SPAN.match("next(batch)")


# -- the tiny cell on the CPU -------------------------------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return build(str(tmp_path_factory.mktemp("bench_sections")))


def test_the_tiny_tree_picks_the_new_metrics_up_through_like(tree):
    with open(os.path.join(tree, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_DEVICE + NEW_SPAN:
        assert "tiny_train" in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert by_name[name]["better"] == "lower"
        assert os.path.exists(os.path.join(
            tree, "benchmark", "layer_metrics", f"{name}.py"))
    assert {by_name[n]["source"] for n in NEW_DEVICE} == {"device_trace"}
    assert {by_name[n]["source"] for n in NEW_SPAN} == {"program_span"}
    # In the issue's order among themselves, wherever later entries stand.
    assert [n for n in by_name if n in NEW_DEVICE + NEW_SPAN] == \
        NEW_DEVICE + NEW_SPAN


def test_tiny_cell_traced_reports_the_span_metrics_and_no_device_metric(
        tree):
    import subprocess

    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_train",
         "--seed", "2900000011", "--seconds", "2",
         "--trace", "1"], cwd=tree, env=env(1), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    for name in NEW_SPAN:
        assert line["metrics"][name]["unit"] == "ms"
        assert 0 < line["metrics"][name]["value"] < 1000
    assert not set(NEW_DEVICE) & set(line["metrics"])
    for name in NEW_DEVICE:
        assert f"per-layer {name}: nothing to read, left out" in p.stdout
    # One span for each dispatched step: the traced steps and the one
    # queued behind the last of them.
    assert "program spans in the trace: {'train.next_batch': 4, " \
           "'train.dispatch': 4, 'train.hooks': 4}" in p.stdout
    assert not os.path.exists(os.path.join(tree, ".bench_trace"))


def test_the_sections_compile_is_the_program_the_trainer_runs(tree,
                                                             monkeypatch):
    """``sections.compile_step`` builds the trainer again, from another
    seed than the run's. The instructions of its compiled text have to be
    those of the step the window runs, by name, result type and scope (here
    on the CPU; on the chip the run checks them against the trace)."""
    import jax
    import numpy as np

    from harness import device, manifest, train_steps

    monkeypatch.setattr(device, "CHECKOUT", tree)
    monkeypatch.setattr(manifest, "CHECKOUT", tree)
    cell = manifest.Cell(manifest.load_manifest(), "tiny_train")
    devices = jax.devices()[:1]
    again = sections.instruction_scopes(
        sections.compile_step(cell, devices).as_text())

    cfg = train_steps.build_program_config(cell, 2900000011)
    trainer, state, _, _ = train_steps.build_trainer(
        cell, cfg, 2900000011, devices)
    gb, s = cfg.train.global_batch, cfg.data.seq_len
    batch = trainer.device_batch({
        "tokens": np.zeros((gb, s + 1), np.int32),
        "loss_mask": np.ones((gb, s), np.float32)})
    real = sections.instruction_scopes(trainer.train_step.lower(
        state, batch, jax.random.PRNGKey(1)).compile().as_text())
    assert again["entry"] == real["entry"]
    assert again["labels"] == real["labels"]
    assert sections.foreign(real["all"], real["labels"],
                            again["labels"]) == []
    assert len(real["entry"]) > 300
    # Of the instructions that carry a name, nearly all fall in a section
    # of the program; the compiler's own copies carry none.
    section_of = sections.sections_of(real)
    named = [n for n, v in real["entry"].items() if v]
    loose = [real["entry"][n] for n in named if section_of[n] == "unscoped"]
    assert len(loose) < 0.05 * len(named), sorted(set(loose))
    # Every section but the kernels' (the XLA attention path runs here).
    found = {section_of[n] for n in named}
    assert found >= {name for name, _ in sections.SECTIONS[:-1]} - {"flash"}
