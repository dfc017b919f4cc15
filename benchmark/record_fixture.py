#!/usr/bin/env python3
"""Record the small trace that the reduction's test reads
(``benchmark/fixtures/small.xplane.pb``): a few calls of the flash attention
forward and backward kernels on one chip, with host spans and an idle gap
between them. Run once on the chip; the file it writes is committed.

    python3 benchmark/record_fixture.py <out-dir>
"""

from __future__ import annotations

import os
import shutil
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from harness import device, xplane
    from deeplearning_cfn_tpu.ops import fused_attention

    device.require_chips(1)
    shape = (2, 4, 1024, 64)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)

    @jax.jit
    def step(q, k, v):
        loss = lambda q, k, v: jnp.sum(fused_attention(
            q, k, v, causal=True, implementation="pallas")
            .astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    jax.block_until_ready(step(q, k, v))
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("fit hook"):
                jax.block_until_ready(step(q, k, v))
            with jax.profiler.TraceAnnotation("next(batch)"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    text = step.lower(q, k, v).compile().as_text()
    with open(os.path.join(out_dir, "small.pallas_calls.json"), "w") as fh:
        import json
        json.dump(xplane.pallas_calls(text), fh)
    print(f"{os.path.getsize(path)} bytes -> {out_dir}/small.xplane.pb")
    print("\n".join(xplane.structure(path, limit=6)))


if __name__ == "__main__":
    main(sys.argv[1])
