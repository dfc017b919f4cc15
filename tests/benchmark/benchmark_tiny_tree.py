"""A temporary copy of the benchmark with tiny configurations, for CPU
rehearsals: the same ``run.py``, harness, references, traffic kinds and
readers, with every size cut so that a cell runs in seconds.

Building the copy is also the proof that the harness is driven by data: the
tiny configurations, traffic mixes and cells are *added* as new files and new
manifest entries, and no file of the benchmark is edited.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GPT = {
    "preset": "gpt_small_lm",
    "n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128,
    "vocab_size": 512,
    "overrides": [
        "model.name=gpt_tiny", "data.vocab_size=512", "data.seq_len=64",
        "model.kwargs.max_len=64",
        "data.synthetic=true", "train.dtype=float32",
        "train.log_every_steps=1", "data.use_native_loader=false"],
    "precision": "float32",
    "limits": {"train_loss_rel": 1e-5, "train_grad_norm_gap": 2e-5,
               "train_change_norm_gap": 1e-2},
}


# What every training cell of the committed manifest is listed on: the ten of
# PRs 23 and 24 and set-up's five of PR 37. A configuration's own test adds
# its cell's own.
EVERY_TRAINING_CELL = {
    "train_tokens_per_s", "step_ms", "device_idle.train", "hbm_peak_gb",
    "head_loss_ms", "blocks_ms", "optimizer_ms", "unscoped_share",
    "input_stall_ms", "dispatch_ms", "first_step_s", "step_trace_s",
    "step_lower_s", "state_init_s", "first_step_other_s"}


def list_like(manifest: dict, name: str, like: str) -> None:
    """Append the cell ``name`` to every ``workloads`` list that names the
    cell ``like``: how a later PR's cell joins the metrics of one it
    resembles."""
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)


def metrics_listing(manifest: dict, cell: str) -> set:
    """The names of the metrics whose ``workloads`` list names ``cell``."""
    return {m["name"] for group in ("end_to_end", "per_layer")
            for m in manifest[group] if cell in m.get("workloads", ())}


def build(dst: str) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` to ``dst`` and add the tiny
    configurations, mixes and cells beside what is there."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    bench = os.path.join(dst, "benchmark")

    def add_config(name, like, changes):
        with open(os.path.join(bench, "configs", f"{like}.json")) as fh:
            cfg = json.load(fh)
        cfg.update(changes, name=name)
        with open(os.path.join(bench, "configs", f"{name}.json"), "w") as fh:
            json.dump(cfg, fh, indent=1)
        shutil.copy(os.path.join(bench, "references", f"{like}.py"),
                    os.path.join(bench, "references", f"{name}.py"))
        manifest["configs"].append({
            "name": name, "source": cfg["source"],
            "file": f"benchmark/configs/{name}.json", "reduced": ["tiny"],
            "why": "CPU rehearsal"})

    def add_traffic(name, like, changes):
        with open(os.path.join(bench, "traffic", f"{like}.json")) as fh:
            t = json.load(fh)
        t.update(changes)
        with open(os.path.join(bench, "traffic", f"{name}.json"), "w") as fh:
            json.dump(t, fh, indent=1)

    def add_cell(name, config, traffic, chips, like):
        manifest["workloads"].append({
            "name": name, "config": config, "traffic": traffic,
            "chips": chips, "why": "CPU rehearsal"})
        list_like(manifest, name, like)

    add_config("gpt2_tiny", "gpt2_small", TINY_GPT)
    add_traffic("tiny_train", "train_packed_1k", {
        "overrides": ["train.global_batch=8", "mesh.data=1",
                      "train.shard_opt_state=false"],
        "num_examples": 64, "trace_steps": 3})
    # The four-chip cell is not in the manifest (the Pallas kernel cannot be
    # partitioned over a data axis, PERF.md section 7). Its parameters are
    # one more traffic file, and the runner's handling of a cell's chips is
    # rehearsed here on virtual devices, where the XLA attention path runs.
    add_traffic("tiny_train_dp4", "train_packed_1k", {
        "overrides": ["train.global_batch=8", "mesh.data=4",
                      "train.shard_opt_state=true"],
        "num_examples": 64, "trace_steps": 3})
    add_cell("tiny_train", "gpt2_tiny", "tiny_train", 1, "gpt2_small_train")
    add_cell("tiny_train_dp4", "gpt2_tiny", "tiny_train_dp4", 4,
             "gpt2_small_train")
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh, indent=1)
    return dst


def env(n_devices: int = 1) -> dict:
    """The environment of a CPU rehearsal: the CPU by name, said to be a
    rehearsal, with ``n_devices`` virtual devices, and the program on the
    path (the copy holds only the benchmark)."""
    e = dict(os.environ)
    e.update(JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="cpu",
             PYTHONPATH=REPO,
             XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    return e
