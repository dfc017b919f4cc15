"""The flash-attention kernels, compiled by the TPU's own compiler for a chip
that is described and not attached (a v5e 2x2), at the shapes the shipped
presets produce. Interpret mode cannot see what this does: a slice that is
not aligned to the tiling, or more fast memory than a kernel may use. A
compile that passes is not a chip run — chip_smoke.py is."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from deeplearning_cfn_tpu.ops.attention import fused_attention  # noqa: E402


@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    # Such a compile is written to the persistent cache but cannot be read
    # back without a chip, so the next one warns: keep the cache off here
    # (the suite's conftest already does; this holds for a lone run too).
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("name,shape,sk,causal", [
    ("gpt_small_lm", (16, 12, 1024, 64), 1024, True),
    ("bert_long_wikipedia", (8, 12, 4096, 64), 4096, False),
    ("head_dim_128", (1, 8, 2048, 128), 2048, True),
    # Several grid tiles, so the sub-tiles' bounds come from program_id: a
    # slice the chip's tiling refuses, or a body that outgrows VMEM, fails
    # here and not on the chip.
    ("gpt_long_lm", (1, 12, 8192, 64), 8192, True),
    ("causal_sq_lt_sk", (2, 12, 1024, 64), 2048, True),
])
@pytest.mark.parametrize("what", ["forward", "grad"])
def test_flash_kernel_compiles_for_v5e(v5e_chip, name, shape, sk, causal,
                                       what):
    assert v5e_chip.device_kind == "TPU v5 lite"
    one_chip = SingleDeviceSharding(v5e_chip)
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(shape[:2] + (sk,) + shape[3:], jnp.bfloat16,
                              sharding=one_chip)

    def attn(q, k, v):
        return fused_attention(q, k, v, causal=causal,
                               implementation="pallas")

    fn = attn if what == "forward" else jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(arg, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
