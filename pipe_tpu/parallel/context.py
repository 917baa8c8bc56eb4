"""Context (sequence) parallelism: shard the sequence axis over the mesh.

The long-context execution layer (SURVEY §5): a ``context`` mesh axis carries
ring attention (``ops.ring_attention``) so sequences longer than one chip's
HBM run exactly, with K/V blocks riding the same ``ppermute``/ICI transport
as the pipeline. Composes with the ``(stage, data)`` mesh — context is just
another named axis.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.ring_attention import ring_attention

__all__ = ["CONTEXT_AXIS", "make_context_mesh", "context_parallel_attention"]

CONTEXT_AXIS = "context"


def make_context_mesh(n_context: int,
                      devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D mesh over the context axis."""
    devices = list(devices if devices is not None else jax.devices())
    if n_context <= 0 or n_context > len(devices):
        raise ValueError(f"need {n_context} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n_context]), (CONTEXT_AXIS,))


def context_parallel_attention(mesh: Mesh, q: jax.Array, k: jax.Array,
                               v: jax.Array, *, causal: bool = True,
                               axis: str = CONTEXT_AXIS,
                               impl: str = "ring") -> jax.Array:
    """Exact attention over globally ``[batch, seq, heads, head_dim]`` inputs
    with ``seq`` sharded over ``axis``; returns the same-sharded output.

    ``impl='ring'`` rotates K/V blocks over the axis (block-sized peak
    memory, any head count); ``impl='ulysses'`` all-to-all-reshards to full
    sequence x heads/c per device (lets the flash kernel run unsharded;
    needs ``heads % axis_size == 0``). Both are exact — see
    ``ops.ulysses_attention`` for the trade-offs.
    """
    if impl == "ring":
        body = partial(ring_attention, axis_name=axis, causal=causal)
    elif impl == "ulysses":
        from ..ops.ulysses_attention import ulysses_attention
        body = partial(ulysses_attention, axis_name=axis, causal=causal)
    else:
        raise ValueError(f"impl must be ring|ulysses, got {impl!r}")
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
