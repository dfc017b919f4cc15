"""Tests for L0/L1: topology catalog, stack store, provisioner, runtime
contract (SURVEY.md §5 tiers 1–2 — the provisioner fixture strategy)."""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from deeplearning_cfn_tpu.config import StackConfig
from deeplearning_cfn_tpu.provision import (
    DryRunProvisioner,
    ProvisionError,
    StackStatus,
    StackStore,
    create_stack,
    delete_stack,
    slice_topology,
)
from deeplearning_cfn_tpu.runtime import cluster as rt


# -- topology ---------------------------------------------------------------


def test_slice_topology_v5p():
    t = slice_topology("v5p-256")
    assert t.num_chips == 256
    assert t.chips_per_host == 4
    assert t.num_hosts == 64
    assert len(t.ici_mesh) == 3
    prod = 1
    for d in t.ici_mesh:
        prod *= d
    assert prod == 256


def test_slice_topology_generations():
    assert slice_topology("v4-8").num_hosts == 2
    assert slice_topology("v5e-16").chips_per_host == 8
    assert slice_topology("v5e-16").num_hosts == 2
    # v2/v3 suffix counts TensorCores (2/chip).
    assert slice_topology("v3-8").num_chips == 4
    assert slice_topology("v3-8").num_hosts == 1


@pytest.mark.parametrize("bad", ["v5p", "x5-8", "v5p-0", "v99-8", "v5e-9999"])
def test_slice_topology_rejects(bad):
    with pytest.raises(ValueError):
        slice_topology(bad)


# -- stack store ------------------------------------------------------------


def test_stack_store_roundtrip(tmp_path):
    store = StackStore(str(tmp_path))
    cfg = StackConfig(name="t1", slice_type="v5p-8", provisioner="dryrun")
    state = DryRunProvisioner().create(cfg)
    store.save(state)
    loaded = store.load("t1")
    assert loaded.name == "t1"
    assert loaded.slice_type == "v5p-8"
    assert loaded.status == StackStatus.CREATE_IN_PROGRESS
    assert len(loaded.hosts) == 2
    assert [s.name for s in store.list()] == ["t1"]
    store.delete("t1")
    assert store.load_or_none("t1") is None


def test_stack_store_rejects_bad_names(tmp_path):
    store = StackStore(str(tmp_path))
    for bad in ["", "../evil", ".hidden"]:
        with pytest.raises(ValueError):
            store._path(bad)


# -- provisioner flows ------------------------------------------------------


def _mk_cfg(tmp_path, **kw):
    defaults = dict(name="demo", slice_type="v5p-8", provisioner="dryrun",
                    state_dir=str(tmp_path), create_timeout_s=60)
    defaults.update(kw)
    return StackConfig(**defaults)


def test_create_stack_happy_path(tmp_path):
    cfg = _mk_cfg(tmp_path)
    seen = []
    state = create_stack(cfg, provisioner=DryRunProvisioner(ready_after_polls=3),
                         on_status=lambda s: seen.append(
                             {h.state for h in s.hosts}),
                         _sleep=lambda s: None)
    assert state.status == StackStatus.CREATE_COMPLETE
    assert state.ready
    assert {h.state for h in state.hosts} == {"READY"}
    # Staged readiness was observed (CREATING before READY).
    assert {"CREATING"} in seen
    # Hostfile written with one address per host — the reference's
    # $DEEPLEARNING_WORKERS_PATH contract.
    hosts = rt.read_hostfile(state.hostfile)
    assert len(hosts) == 2
    # Store agrees.
    assert StackStore(str(tmp_path)).load("demo").ready


def test_create_stack_duplicate_rejected(tmp_path):
    cfg = _mk_cfg(tmp_path)
    create_stack(cfg, provisioner=DryRunProvisioner(), _sleep=lambda s: None)
    with pytest.raises(ProvisionError, match="already exists"):
        create_stack(cfg, provisioner=DryRunProvisioner(),
                     _sleep=lambda s: None)


def test_create_stack_partial_failure(tmp_path):
    """A host that never becomes healthy fails the stack — the
    WaitCondition-timeout contract: no partial cluster is ever handed out."""
    cfg = _mk_cfg(tmp_path)
    with pytest.raises(ProvisionError, match="failed to assemble"):
        create_stack(cfg, provisioner=DryRunProvisioner(fail_hosts=[1]),
                     _sleep=lambda s: None)
    assert StackStore(str(tmp_path)).load("demo").status == \
        StackStatus.CREATE_FAILED


def test_create_stack_timeout(tmp_path):
    cfg = _mk_cfg(tmp_path, create_timeout_s=0)
    with pytest.raises(ProvisionError, match="timed out"):
        create_stack(cfg, provisioner=DryRunProvisioner(ready_after_polls=99),
                     _sleep=lambda s: None)


def test_delete_stack(tmp_path):
    cfg = _mk_cfg(tmp_path)
    state = create_stack(cfg, provisioner=DryRunProvisioner(),
                         _sleep=lambda s: None)
    hostfile = state.hostfile
    assert os.path.exists(hostfile)
    delete_stack("demo", store=StackStore(str(tmp_path)))
    assert not os.path.exists(hostfile)
    assert StackStore(str(tmp_path)).load_or_none("demo") is None


# -- runtime contract -------------------------------------------------------


def test_hostfile_roundtrip(tmp_path):
    path = str(tmp_path / "hosts")
    rt.write_hostfile(path, ["10.0.0.1", "10.0.0.2"])
    assert rt.read_hostfile(path) == ["10.0.0.1", "10.0.0.2"]


def test_cluster_env_and_back(tmp_path):
    hostfile = rt.write_hostfile(str(tmp_path / "hosts"),
                                 ["10.0.0.1", "10.0.0.2", "10.0.0.3"])
    spec = rt.ClusterSpec(hosts=["10.0.0.1", "10.0.0.2", "10.0.0.3"],
                          chips_per_host=4, hostfile=hostfile)
    env = rt.cluster_env(spec, process_id=2)
    assert env[rt.ENV_WORKERS_COUNT] == "3"
    assert env[rt.ENV_COORDINATOR] == "10.0.0.1:8476"
    assert env[rt.ENV_PROCESS_ID] == "2"
    # A worker process reconstructs the same spec from its environment.
    spec2 = rt.current_cluster(env)
    assert spec2 is not None
    assert spec2.hosts == spec.hosts
    assert spec2.process_id == 2
    assert spec2.coordinator == "10.0.0.1:8476"
    assert spec2.is_multi_host


def test_current_cluster_absent_contract():
    assert rt.current_cluster({}) is None


def test_initialize_single_host_noop():
    spec = rt.initialize(rt.ClusterSpec(hosts=["localhost"]))
    assert not spec.is_multi_host


def test_cluster_spec_validation():
    with pytest.raises(ValueError):
        rt.ClusterSpec(hosts=[]).validate()
    with pytest.raises(ValueError):
        rt.ClusterSpec(hosts=["a"], process_id=1).validate()


# -- real multi-process rendezvous -----------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_rendezvous(tmp_path):
    """Two real OS processes join through the env contract and see each
    other's devices — jax.distributed over the launcher's env block, the
    rebuild's MPI-rendezvous replacement, minus TPUs."""
    port = _free_port()
    spec = rt.ClusterSpec(hosts=["127.0.0.1", "127.0.0.1"],
                          coordinator_port=port)
    script = textwrap.dedent("""
        import jax
        from deeplearning_cfn_tpu.runtime import initialize
        spec = initialize(timeout_s=60)
        assert spec.is_multi_host, spec
        assert jax.process_count() == 2, jax.process_count()
        total = jax.device_count()
        local = jax.local_device_count()
        assert total == 2 * local, (total, local)
        print("RENDEZVOUS_OK", jax.process_index(), total)
    """)
    env_base = {k: v for k, v in os.environ.items()}
    env_base["JAX_PLATFORMS"] = "cpu"
    # One fake device per process keeps startup fast.
    env_base["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    procs = []
    try:
        for pid in range(2):
            env = {**env_base, **rt.cluster_env(spec, pid)}
            procs.append(subprocess.Popen(
                [sys.executable, "-c", script], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                cwd=os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))),
            ))
        outs = [p.communicate(timeout=120)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}"
        assert "RENDEZVOUS_OK" in out


# ONE config for both sides of the 1-proc vs 2-proc equivalence (the
# comparison is vacuous if the two runs can drift apart): built from this
# override list by `_two_proc_cfg` in-test and by the worker (which
# receives it via DLCFN_TEST_CFG).
_TWO_PROC_OVERRIDES = [
    "model.num_classes=10", "data.image_size=16",
    "data.num_train_examples=32", "data.prefetch=0",
    "train.global_batch=32", "train.dtype=float32",
    "optimizer.name=momentum", "optimizer.momentum=0.9",
    "schedule.name=constant", "schedule.base_lr=0.05",
    "schedule.warmup_steps=0",
]


def _two_proc_cfg(overrides):
    from deeplearning_cfn_tpu.config import (
        DataConfig, ExperimentConfig, ModelConfig, apply_overrides)

    cfg = ExperimentConfig(
        model=ModelConfig(name="resnet20"),
        data=DataConfig(name="imagenet"))
    return apply_overrides(cfg, overrides)


_TRAIN_WORKER = """
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning_cfn_tpu.runtime import initialize
spec = initialize(timeout_s=60)
assert jax.process_count() == 2

import numpy as np
from deeplearning_cfn_tpu.config import (DataConfig, ExperimentConfig,
    ModelConfig, apply_overrides)
from deeplearning_cfn_tpu.data import build_pipeline
from deeplearning_cfn_tpu.parallel.mesh import build_mesh, local_batch_size
from deeplearning_cfn_tpu.train import create_train_state
from deeplearning_cfn_tpu.train.optim import build_optimizer, build_schedule
from deeplearning_cfn_tpu.train.task import build_task
from deeplearning_cfn_tpu.train.trainer import Trainer

out_dir = os.environ["DLCFN_TEST_OUT"]
GB, STEPS = 32, 3
cfg = apply_overrides(
    ExperimentConfig(model=ModelConfig(name="resnet20"),
                     data=DataConfig(name="imagenet")),
    json.loads(os.environ["DLCFN_TEST_CFG"]))
assert cfg.train.global_batch == GB
mesh = build_mesh(cfg.mesh)
lb = local_batch_size(GB, mesh)
assert lb == GB // 2, lb  # each host feeds exactly half

pipe = build_pipeline(cfg.data, lb, 10, seed=0, train=True)
pidx = jax.process_index()
with open(os.path.join(out_dir, f"idx_{pidx}.json"), "w") as f:
    json.dump([int(i) for i in pipe._epoch_indices(0)], f)

task = build_task(cfg)
tx = build_optimizer(cfg.optimizer, build_schedule(cfg.schedule, 100, GB, 0))
state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
tr = Trainer(cfg, task.loss_fn, tx, mesh=mesh, donate=False)
it = pipe.epochs()
for _ in range(STEPS):
    state, m = tr.train_step(state, tr.device_batch(next(it)),
                             jax.random.PRNGKey(1))
loss = float(m["loss"])
if pidx == 0:
    leaves = jax.tree_util.tree_leaves(jax.device_get(state.params))
    np.savez(os.path.join(out_dir, "params_2proc.npz"),
             **{str(i): np.asarray(a) for i, a in enumerate(leaves)})
print("TRAIN2P_OK", pidx, loss)
"""


@pytest.mark.slow
def test_two_process_train_shards_and_matches_single(tmp_path):
    """The launcher→trainer seam end to end (r03 verdict, Next #7): two
    real processes train CIFAR-shaped ResNet-20 for 3 steps and must (a)
    each feed ONLY their addressable half of the shared epoch permutation,
    (b) cover the global batch exactly once between them, and (c) land on
    the same final params as the same run on one 8-device process — the
    multi-HOST analogue of the in-process DP equivalence tests."""
    port = _free_port()
    spec = rt.ClusterSpec(hosts=["127.0.0.1", "127.0.0.1"],
                          coordinator_port=port)
    env_base = dict(os.environ)
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env_base["DLCFN_TEST_OUT"] = str(tmp_path)
    import json as _json

    env_base["DLCFN_TEST_CFG"] = _json.dumps(_TWO_PROC_OVERRIDES)
    procs = []
    try:
        for pid in range(2):
            env = {**env_base, **rt.cluster_env(spec, pid)}
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _TRAIN_WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                cwd=os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))),
            ))
        outs = [p.communicate(timeout=560)[0] for p in procs]
    finally:
        # A deadlocked rendezvous must not orphan workers spinning in the
        # collective client (and holding the coordinator port).
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
        assert "TRAIN2P_OK" in out

    # (a)+(b): disjoint halves covering the dataset exactly once.
    import json as _json

    idx0 = _json.load(open(tmp_path / "idx_0.json"))
    idx1 = _json.load(open(tmp_path / "idx_1.json"))
    assert len(idx0) == len(idx1) == 16
    assert set(idx0).isdisjoint(idx1)
    assert set(idx0) | set(idx1) == set(range(32))

    # (c): the same run, single process on the in-test 8-device mesh —
    # the SAME config object both sides (shared override list).
    import jax

    from deeplearning_cfn_tpu.data import build_pipeline
    from deeplearning_cfn_tpu.parallel.mesh import build_mesh, \
        local_batch_size
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = _two_proc_cfg(_TWO_PROC_OVERRIDES)
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg)
    tx = build_optimizer(cfg.optimizer,
                         build_schedule(cfg.schedule, 100, 32, 0))
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
    tr = Trainer(cfg, task.loss_fn, tx, mesh=mesh, donate=False)
    pipe = build_pipeline(cfg.data, local_batch_size(32, mesh), 10,
                          seed=0, train=True)
    it = pipe.epochs()
    for _ in range(3):
        state, m = tr.train_step(state, tr.device_batch(next(it)),
                                 jax.random.PRNGKey(1))
    ref_leaves = jax.tree_util.tree_leaves(jax.device_get(state.params))
    with np.load(tmp_path / "params_2proc.npz") as z:
        got = [z[str(i)] for i in range(len(ref_leaves))]
    for i, (a, b) in enumerate(zip(ref_leaves, got)):
        np.testing.assert_allclose(
            np.asarray(a), b, rtol=1e-4, atol=1e-6,
            err_msg=f"leaf {i} diverged between 1-proc and 2-proc runs")


# -- GCP provisioner (offline: gcloud invocations pinned, not run) ----------


class _FakeGcloud:
    """Capture GcpProvisioner._run invocations and script its outputs."""

    def __init__(self, outputs=()):
        self.calls = []
        self.outputs = list(outputs)

    def __call__(self, *args):
        self.calls.append(args)
        return self.outputs.pop(0) if self.outputs else "{}"


def _gcp(monkeypatch, outputs=()):
    from deeplearning_cfn_tpu.provision.provisioner import GcpProvisioner

    monkeypatch.setattr("shutil.which", lambda name: "/usr/bin/gcloud")
    prov = GcpProvisioner()
    fake = _FakeGcloud(outputs)
    prov._run = fake
    return prov, fake


def test_gcp_create_command_line(monkeypatch):
    """The create call must carry every config knob — this is the CFN
    template-parameters contract, TPU-shaped."""
    prov, fake = _gcp(monkeypatch)
    cfg = StackConfig(name="prod", slice_type="v5p-16", zone="us-east5-a",
                      project="my-proj", runtime_version="tpu-vm-custom",
                      preemptible=True, provisioner="gcp")
    state = prov.create(cfg)
    (args,) = fake.calls
    assert args[:5] == ("compute", "tpus", "tpu-vm", "create", "prod")
    assert "--zone=us-east5-a" in args
    assert "--version=tpu-vm-custom" in args
    assert "--project=my-proj" in args
    assert "--preemptible" in args
    assert "--async" in args
    assert any(a.startswith("--accelerator-type=") for a in args)
    assert state.status == StackStatus.CREATE_IN_PROGRESS
    assert len(state.hosts) == 4  # v5p-16 = 4 hosts


def test_gcp_refresh_parses_describe(monkeypatch):
    import json as _json

    desc = _json.dumps({
        "state": "READY",
        "networkEndpoints": [
            {"ipAddress": "10.0.0.2",
             "accessConfig": {"externalIp": "34.1.2.3"}},
            {"ipAddress": "10.0.0.3", "accessConfig": {}},
        ],
    })
    prov, fake = _gcp(monkeypatch, outputs=[desc])
    from deeplearning_cfn_tpu.provision import StackState

    state = StackState(name="prod", slice_type="v5p-8", zone="z")
    state = prov.refresh(state)
    assert [h.internal_ip for h in state.hosts] == ["10.0.0.2", "10.0.0.3"]
    assert [h.external_ip for h in state.hosts] == ["34.1.2.3", ""]
    assert all(h.state == "READY" for h in state.hosts)
    assert fake.calls[0][:5] == ("compute", "tpus", "tpu-vm", "describe",
                                 "prod")


def test_gcp_delete_command_line(monkeypatch):
    from deeplearning_cfn_tpu.provision import StackState

    prov, fake = _gcp(monkeypatch)
    state = StackState(name="prod", slice_type="v5p-8", zone="z",
                       project="my-proj")
    prov.delete(state)
    (args,) = fake.calls
    assert args[:5] == ("compute", "tpus", "tpu-vm", "delete", "prod")
    assert "--quiet" in args and "--project=my-proj" in args


def test_gcp_run_raises_on_failure(monkeypatch):
    from deeplearning_cfn_tpu.provision.provisioner import GcpProvisioner

    monkeypatch.setattr("shutil.which", lambda name: "/usr/bin/gcloud")
    prov = GcpProvisioner()

    class Proc:
        returncode = 1
        stderr = "quota exceeded"
        stdout = ""

    monkeypatch.setattr("subprocess.run", lambda *a, **k: Proc())
    with pytest.raises(ProvisionError, match="quota exceeded"):
        prov._run("compute", "tpus", "list")
