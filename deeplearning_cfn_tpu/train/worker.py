"""Per-host worker entry point: ``python -m deeplearning_cfn_tpu.train.worker``.

This is the process the launcher fans to every slice host (SURVEY.md §4.4) —
the analogue of the per-rank ``python train.py`` that mpirun/launch.py spawned
in the reference. It joins the rendezvous (L1), then runs the experiment; all
distribution from here down is mesh shardings inside the compiled step.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from ..config import apply_overrides
from ..presets import get_preset
from ..runtime import initialize, start_profiler_server
from .run import run_experiment


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="dlcfn-tpu-worker",
        description="per-host training worker (launched by `dlcfn-tpu train`)",
    )
    parser.add_argument("--preset", required=True)
    parser.add_argument("--max-steps", type=int, default=None)
    parser.add_argument("--profiler-port", type=int, default=0,
                        help="start a jax.profiler server on this port")
    parser.add_argument("overrides", nargs="*",
                        help="config overrides, e.g. train.global_batch=256")
    args = parser.parse_args(argv)

    from ..runtime.platform import require_accelerator

    spec = initialize()  # no-op single-host; rendezvous when contract present
    # After the rendezvous: looking at the devices initialises the backend,
    # which jax.distributed must precede.
    require_accelerator()
    if args.profiler_port:
        start_profiler_server(args.profiler_port)

    cfg = apply_overrides(get_preset(args.preset), args.overrides)
    final = run_experiment(cfg, max_steps=args.max_steps)
    import jax

    if jax.process_index() == 0:
        print(f"[dlcfn-tpu] worker {spec.process_id} final metrics: "
              f"{ {k: round(v, 4) for k, v in final.items()} }")
    return 0


if __name__ == "__main__":
    sys.exit(main())
