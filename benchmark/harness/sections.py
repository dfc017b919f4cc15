"""Device time of the train step by section of the program.

On this runtime a device event of the trace carries its HLO text and no
``op_name``. The program's scopes (flax's module paths, and the
``jax.named_scope``s ``docs/OBSERVABILITY.md`` lists) reach a number the way
the Pallas kernels' names already do: instruction name in the trace ->
the same instruction in the compiled step's text -> its ``op_name`` -> the
first pattern of ``SECTIONS`` that matches. Every operation falls in exactly
one section, so the sections sum to what ``Trace.op_seconds`` sums.

The text comes from building the cell's trainer again with the runner's own
``build_trainer`` and lowering its ``train_step`` as the runner does: the
program the window ran by construction, and after the window a hit in the
persistent compile cache. That is still checked, because names like
``fusion.22`` exist in any variant of the step: an operation of the trace
whose name the text lacks, or has with another result type, is not this
text's. A little of that is another program's (the window's marker) and is
counted ``unscoped``; more than ``FOREIGN_LIMIT`` of the operations' time
means the text is not the program that ran, and nothing is reported.
"""

from __future__ import annotations

import re
import time
from typing import Dict, Iterable, List, Optional, Tuple

from . import xplane

# (section, pattern over the ``op_name``); the first match wins, and the
# last matches everything: an operation no scope of the program or of a flax
# module names. ``dropout`` is looked for in everything a fusion holds, not
# only in its root's ``op_name``: on the chip every mask is drawn inside the
# fusion that consumes it, so the section is the operations that draw or
# apply a mask, with what else they do. ``step_rng`` (the trainer's fold of
# the key with the step) is counted with the masks. ``flash`` is the three
# Pallas kernels alone, which have their own metrics; what else stands
# under ``core_attention`` (transposes, padding, the backward's delta; the
# whole core where the XLA path runs) is "the rest of ``self_attn``".
SECTIONS: Tuple[Tuple[str, str], ...] = (
    ("dropout", r"/Dropout_\d+\b|/dropout\b|\bstep_rng\b"),
    ("flash", r"core_attention/flash_(fwd|bwd_dkdv|bwd_dq)\b"),
    ("head", r"\blm_head\b"),
    ("loss", r"\blm_loss\b"),
    ("optimizer", r"/optimizer\b"),
    ("attn_proj", r"/self_attn\b"),
    ("mlp", r"/mlp\b"),
    ("norm", r"_norm\b"),
    ("embed", r"\._embed\b"),
    ("unscoped", r""),
)
_COMPILED = [(name, re.compile(rx)) for name, rx in SECTIONS]
_DROPOUT = _COMPILED[0][1].search
# Share of the operations' time that may belong to no instruction of the
# text before the text counts as another program's.
FOREIGN_LIMIT = 0.005

_COMPUTATION = re.compile(
    r"^(?P<entry>ENTRY )?%?(?P<name>[\w.\-]+) \(.*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<rest>.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# Not operations of their own: they take no device time and XLA gives them
# the name of an argument, not of a scope.
_NO_WORK = re.compile(
    r"^\S+ (?:parameter|constant|get-tuple-element|tuple|bitcast)\(")


def classify(op_name: str, inside: Iterable[str] = ()) -> str:
    """The section of an instruction with this ``op_name`` that holds
    instructions with the ``op_name``s ``inside`` (a fusion's; none for a
    plain instruction)."""
    if any(map(_DROPOUT, inside)):
        return SECTIONS[0][0]
    return next(name for name, rx in _COMPILED if rx.search(op_name))


def instruction_scopes(hlo_text: str) -> Dict[str, Dict]:
    """``{"all": {instruction: op_name}, "entry": {instruction: op_name},
    "inside": {instruction: [op_name of each fused instruction]},
    "labels": {instruction: name and result type}}`` of a compiled
    program's text. ``all`` holds every computation's instructions (names
    are unique in a module, and the trace shows the bodies of loops by their
    own names); ``entry`` the entry computation's that do work; ``inside``
    what a fusion holds, whose own ``op_name`` is its root's; ``labels``
    what ``Trace.labels`` has for the same instruction in a trace. An
    instruction without metadata has the ``op_name`` ""."""
    scopes: Dict[str, str] = {}
    entry: Dict[str, str] = {}
    labels: Dict[str, str] = {}
    members: Dict[str, List[str]] = {}
    calls: Dict[str, str] = {}
    computation, in_entry = "", False
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group("name")
            in_entry = bool(head.group("entry"))
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.group("name"), m.group("rest")
        found = _OP_NAME.search(rest)
        scopes[name] = found.group(1) if found else ""
        labels[name] = xplane.label(f"{name} = {rest}")
        members.setdefault(computation, []).append(scopes[name])
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        if in_entry and not _NO_WORK.match(rest):
            entry[name] = scopes[name]
    inside = {name: members.get(c, []) for name, c in calls.items()}
    return {"all": scopes, "entry": entry, "inside": inside,
            "labels": labels}


def sections_of(scopes: Dict[str, Dict]) -> Dict[str, str]:
    """``{instruction: section}`` for every instruction of a text."""
    return {name: classify(op_name, scopes["inside"].get(name, ()))
            for name, op_name in scopes["all"].items()}


def foreign(op_names: Iterable[str], trace_labels: Dict[str, str],
            text_labels: Dict[str, str]) -> List[str]:
    """The operations of a trace that are not instructions of the text:
    the text lacks the name, or has it with another result type."""
    return sorted(n for n in op_names if n not in text_labels
                  or trace_labels.get(n, text_labels[n]) != text_labels[n])


def by_section(op_seconds: Dict[str, float], section_of: Dict[str, str]
               ) -> Tuple[Dict[str, float], List[str]]:
    """Seconds by section, every section present, and the operations
    ``section_of`` lacks (counted ``unscoped``, never dropped)."""
    out = {name: 0.0 for name, _ in SECTIONS}
    missing = []
    for name, seconds in op_seconds.items():
        if name not in section_of:
            missing.append(name)
        out[section_of.get(name, SECTIONS[-1][0])] += seconds
    return out, missing


def compile_step(cell, devices):
    """The cell's train step, built and lowered as the runner does it: its
    ``build_trainer`` (the state's shardings), the trainer's own
    ``device_batch`` and ``train_step`` (the donation), a key that was
    never placed. The seed moves values, never shapes."""
    import jax
    import numpy as np

    from . import train_steps

    cfg = train_steps.build_program_config(cell, seed=0)
    trainer, state, _, _ = train_steps.build_trainer(cell, cfg, 0,
                                                     list(devices))
    gb, s = cfg.train.global_batch, cfg.data.seq_len
    batch = trainer.device_batch({
        "tokens": np.zeros((gb, s + 1), np.int32),
        "loss_mask": np.ones((gb, s), np.float32)})
    rng = jax.random.split(jax.random.PRNGKey(cfg.train.seed), 3)[2]
    return trainer.train_step.lower(state, batch, rng).compile()


def read(ctx) -> Optional[Dict[str, float]]:
    """Device 0's seconds in the traced window by section, or ``None``
    where there is no trace or the compiled text is not the program the
    trace shows. The readers share one compile: the result is kept in
    ``ctx``."""
    if ctx["trace"] is None:
        return None
    if "sections" not in ctx:
        ctx["sections"] = _read(ctx)
    return ctx["sections"]


def _read(ctx) -> Optional[Dict[str, float]]:
    import jax

    from .device import CompileEvents

    say, trace, window = ctx["say"], ctx["trace"], ctx["window"]
    events = CompileEvents()
    t0 = time.perf_counter()
    compiled = compile_step(ctx["cell"],
                            jax.devices()[:ctx["device"]["count"]])
    cache = events.mark()
    scopes = instruction_scopes(compiled.as_text())
    ops = trace.op_seconds(0, window)
    total = sum(ops.values())
    strangers = foreign(ops, trace.labels, scopes["labels"])
    strange = sum(ops[n] for n in strangers)
    say(f"sections: the step compiled again in "
        f"{time.perf_counter() - t0:.1f} s (persistent cache "
        f"{cache['cache_hits']} hits of {cache['cache_requests']} "
        f"requests); {len(ops) - len(strangers)} of the trace's {len(ops)} "
        f"operations are instructions of its text, by name and result type"
        + (f"; not in the text, {100 * strange / total:.3f} % of the "
           f"operations' time: "
           + ", ".join(f"{trace.labels.get(n, n)} (the text has "
                       f"{scopes['labels'].get(n, 'no such name')})"
                       for n in strangers[:8])
           if strangers else ""))
    if strange > FOREIGN_LIMIT * total:
        say(f"sections: over {100 * FOREIGN_LIMIT} % of the operations' "
            f"time is in none of the text's instructions: the text is not "
            f"the program the window ran, and no section is reported")
        return None
    section_of = sections_of(scopes)
    for name in strangers:
        section_of.pop(name, None)
    seconds, _ = by_section(ops, section_of)
    steps = ctx["run"]["steps"]
    ms = lambda v: 1e3 * v / steps
    busy = sum(e - s for s, e in trace.busy_intervals(0, window)) / 1e9
    say("sections, device 0, ms a step (share of the operations' time): "
        + ", ".join(f"{k} {ms(v):.2f} ({100 * v / total:.1f} %)"
                    for k, v in seconds.items()))
    kernels, _ = trace.events_named(
        {k["name"] for k in ctx["run"].get("pallas_calls", [])}, 0, window)
    say(f"sections: head_loss_ms + dropout_ms + blocks_ms + optimizer_ms + "
        f"unscoped {ms(total - seconds['flash']):.2f} + the flash kernels "
        f"{ms(kernels):.2f} = {ms(total - seconds['flash'] + kernels):.2f} "
        f"ms a step against {ms(busy):.2f} busy "
        f"({100 * ((total - seconds['flash'] + kernels) / busy - 1):+.2f} %)")
    # Where the masks are: in operations of dropout's own, or inside a
    # fusion whose root belongs to another layer, which does its work too.
    held = {name: 0.0 for name, _ in SECTIONS}
    for n, v in ops.items():
        if section_of.get(n) == "dropout":
            held[classify(scopes["all"][n])] += v
    say("sections: dropout by the section of each operation's root, ms a "
        "step: " + (", ".join(f"{k} {ms(v):.2f}" for k, v in held.items()
                              if v) or "none")
        + " (under `dropout` the operation is the masks' alone; elsewhere "
          "it also does that layer's work, so dropout_ms bounds the masks' "
          "cost from above)")
    loose = sorted(((v, n) for n, v in ops.items()
                    if section_of.get(n, "unscoped") == "unscoped"),
                   reverse=True)[:6]
    if loose:
        say("sections: longest unscoped operations, ms a step: "
            + ", ".join(f"{trace.labels.get(n, n)} {ms(v):.3f} "
                        f"[{scopes['all'].get(n, '(not in the text)')}]"
                        for v, n in loose))
    return seconds


def ms_per_step(ctx, *names: str) -> Optional[float]:
    """Milliseconds of device time a step in the named sections."""
    seconds = read(ctx)
    if seconds is None:
        return None
    return 1e3 * sum(seconds[n] for n in names) / ctx["run"]["steps"]
