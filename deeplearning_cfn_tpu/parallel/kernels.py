"""Pallas kernels on a mesh of more than one device.

A ``pallas_call`` is a custom call the partitioner cannot split (``Mosaic
kernels cannot be automatically partitioned. Please wrap the call in a
shard_map``), so every kernel of a step that is compiled for a mesh runs under
a ``shard_map`` over the axes the batch is sharded on: each device runs the
kernel over its own rows, as it would alone. :func:`shard_rows` is that one
wrapper, used by ``ops/attention.py`` (the three flash kernels, through the
call's own VJP), ``ops/rope.py`` (two), ``ops/ssd.py`` (two, with the running
sums beside them), ``ops/conv.py`` (two) and ``models/moe.py`` (megablox's
``gmm`` / ``tgmm`` inside the expert layer's per-rank part). **On a mesh of
one device, or told no mesh, it returns the function as it is**: nothing is
entered and the program compiled is the one compiled without it.

``parallel.shard_map.calls``, labelled ``kernel=flash|rope|gmm|ssd|conv``, counts
the wrappers built while a step is traced (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.trace import get_tracer
from .mesh import BATCH_AXES


def batch_axes_of(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    """The axes of ``mesh`` that carry batch shards (``BATCH_AXES`` of more
    than one device), in the mesh's order: what ``batch_sharding`` puts on
    dimension 0. Empty for no mesh and for a mesh of one device."""
    if mesh is None:
        return ()
    return tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)


def rows_spec(axes: Sequence[str], ndim: int, dim: int = 0) -> P:
    """Dimension ``dim`` of ``ndim`` over ``axes`` jointly, the rest whole;
    nothing sharded where there are no axes."""
    spec = [None] * ndim
    if axes:
        spec[dim] = tuple(axes) if len(axes) > 1 else axes[0]
    return P(*spec)


def shard_rows(fn: Callable, mesh: Optional[Mesh], kernel: str,
               in_specs, out_specs, scope: Optional[str] = None) -> Callable:
    """``fn`` run by every device of ``mesh`` over its own shard of the
    arguments (``in_specs``, ``out_specs``: ``PartitionSpec``s, as
    ``jax.shard_map`` takes them), or ``fn`` itself where no axis of the
    mesh carries batch shards (one device; no mesh). The replication check is
    off: a ``pallas_call`` states nothing about how its result varies over
    the mesh. ``scope`` is opened again inside: the wrapper's own name
    (``shard_map``) stands in an operation's ``op_name`` between the caller's
    scope and the kernel's, and a trace's reader that looks for
    ``<scope>/<kernel>`` finds it as on one device."""
    if not batch_axes_of(mesh):
        return fn
    get_tracer().registry.counter(
        "parallel.shard_map.calls",
        "shard_map wrappers built round Pallas kernels while a step is "
        "traced, by kernel",
    ).inc(kernel=kernel)
    if scope is not None:
        fn = jax.named_scope(scope)(fn)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
