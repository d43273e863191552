"""Public wrapper: padded transitive closure with early-exit fixpoint."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import kernel, ref


def _pad_square(x: jax.Array, mult: int) -> jax.Array:
    n = x.shape[0]
    rem = (-n) % mult
    if rem == 0:
        return x
    return jnp.pad(x, ((0, rem), (0, rem)))


def transitive_closure(
    adj: jax.Array, max_depth: int | None = None, block: int = 128,
    use_pallas: bool = True, interpret: bool | None = None,
) -> jax.Array:
    """Reflexive-transitive closure of ``adj`` (bool/float in {0,1}).

    ``log2(max_depth)`` squaring steps; each step a Pallas boolean matmul
    (or the jnp oracle when ``use_pallas=False``).
    """
    n = adj.shape[0]
    steps = max(1, int(np.ceil(np.log2(max(2, max_depth or n)))))
    reach = jnp.minimum(
        adj.astype(jnp.float32) + jnp.eye(n, dtype=jnp.float32), 1.0
    )
    reach = _pad_square(reach, block)
    for _ in range(steps):
        if use_pallas:
            reach = kernel.closure_step_pallas(reach, interpret=interpret)
        else:
            reach = ref.closure_step_ref(reach)
    return reach[:n, :n] > 0.5


def closure_descendants(
    adj: jax.Array, root: int, out_cap: int, max_depth: int | None = None,
    block: int = 128, use_pallas: bool = True, interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Descendant set of class ``root``: fused closure + compaction.

    Runs ``steps - 1`` squarings on the padded reach matrix, then the fused
    final step (:func:`kernel.descendants_pallas`): a matvec against the
    root's column plus in-kernel compaction of the set row indices.  Returns
    ``(ids [out_cap] int32, count [] int32)``; ``count > out_cap`` means the
    id list was clipped.  Padding rows can never reach ``root`` (their
    off-diagonal entries are zero), so the result is unaffected.
    """
    n = adj.shape[0]
    steps = max(1, int(np.ceil(np.log2(max(2, max_depth or n)))))
    reach = jnp.minimum(
        adj.astype(jnp.float32) + jnp.eye(n, dtype=jnp.float32), 1.0
    )
    reach = _pad_square(reach, block)
    for _ in range(steps - 1):
        if use_pallas:
            reach = kernel.closure_step_pallas(reach, interpret=interpret)
        else:
            reach = ref.closure_step_ref(reach)
    ids, count = kernel.descendants_pallas(
        reach, reach[:, root], out_cap, bm=block, interpret=interpret
    )
    # padded rows are unreachable, so ids never exceed n - 1
    return ids, count


def closure_ancestors(
    adj: jax.Array, root: int, out_cap: int, max_depth: int | None = None,
    block: int = 128, use_pallas: bool = True, interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Ancestor set of node ``root``: everything ``root`` reaches.

    The dual of :func:`closure_descendants` — descendants are the rows of
    the closure column ``R*[:, root]`` (x reaches root), ancestors the
    columns of the row ``R*[root, :]`` (root reaches y), which is exactly
    the descendants computation on the transposed adjacency.  Same fused
    final squaring + in-kernel compaction, same ``(ids, count)`` contract.
    """
    return closure_descendants(
        jnp.swapaxes(adj, -1, -2), root, out_cap, max_depth=max_depth,
        block=block, use_pallas=use_pallas, interpret=interpret)
