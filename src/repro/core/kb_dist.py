"""Distributed KB join: the KB partition itself divided across devices.

The paper's central deployment move is "divide the KB through different
machines".  Within one SCEP operator this becomes: row-shard the (sorted)
triple store over the ``model`` mesh axis (``kb.shard_rows``), evaluate the
window⋈KB join **locally per shard** with ``shard_map``, and union the
per-shard binding rows.  Because the union is a concatenation along the
sharded row axis, the join itself needs NO collectives — only the overflow
flag is ``psum``-reduced (a single bool).  Each shard owns a contiguous key
range (both KB views are key-sorted), so the probe method's ``searchsorted``
stays correct per shard.

Capacity semantics: each shard compacts its local matches into
``out_cap // n_shards`` rows; a shard-local overflow is reported even when a
global join would have fit (the price of the static layout — size
``out_cap`` to the expected match skew, exactly like sizing Kafka partition
consumers in the paper's deployment).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import algebra
from .kb import KnowledgeBase
from .pattern import Bindings, CompiledPattern


def kb_join_sharded(
    bind: Bindings,
    kb_blocks: KnowledgeBase,      # leaves [n_shards, per] (kb.shard_rows)
    pat: CompiledPattern,
    out_cap: int,
    mesh: Mesh,
    axis: str = "model",
    method: str = "scan",
    k_max: int = 8,
    use_pallas: bool = False,
    fuse_compaction: bool = False,
    bm: int | None = None,
    bn: int | None = None,
) -> Bindings:
    """Join replicated bindings against a row-sharded KB partition.

    ``fuse_compaction`` runs the fused join->compaction pipeline *inside*
    each shard's local join: every device compacts its own matches into its
    ``out_cap // n_shards`` slice, so the no-collective union (a reshape
    along the sharded row axis) is unchanged — fusion is purely shard-local.
    """
    n = mesh.shape[axis]
    assert out_cap % n == 0, (out_cap, n)
    per_cap = out_cap // n

    def local(cols, valid, overflow, kb_block):
        kb_local = jax.tree.map(lambda a: a[0], kb_block)
        b = Bindings(cols, valid, overflow)
        out = algebra.kb_join(b, kb_local, pat, per_cap, method=method,
                              k_max=k_max, use_pallas=use_pallas,
                              fuse_compaction=fuse_compaction, bm=bm, bn=bn)
        # overflow is global info: reduce the one bool over the KB axis
        ovf = jax.lax.psum(out.overflow.astype(jnp.int32), axis) > 0
        return out.cols[None], out.valid[None], ovf

    kb_spec = jax.tree.map(lambda _: P(axis), kb_blocks)
    cols, valid, overflow = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(), kb_spec),
        out_specs=(P(axis), P(axis), P()),
        check_vma=False,
    )(bind.cols, bind.valid, bind.overflow, kb_blocks)
    # shard-major union: [n, per_cap, nv] -> [out_cap, nv]
    return Bindings(cols.reshape(out_cap, bind.num_vars),
                    valid.reshape(out_cap), overflow)


def kb_join_blocks_reference(
    bind: Bindings, kb_blocks: KnowledgeBase, pat: CompiledPattern,
    out_cap: int, n: int, method: str = "scan", k_max: int = 8,
    use_pallas: bool = False, fuse_compaction: bool = False,
) -> Bindings:
    """Oracle: the same per-block join/union evaluated sequentially."""
    per_cap = out_cap // n
    cols, valids, ovf = [], [], bind.overflow
    for i in range(n):
        kb_local = jax.tree.map(lambda a: a[i], kb_blocks)
        out = algebra.kb_join(bind, kb_local, pat, per_cap, method=method,
                              k_max=k_max, use_pallas=use_pallas,
                              fuse_compaction=fuse_compaction)
        cols.append(out.cols)
        valids.append(out.valid)
        ovf = ovf | out.overflow
    return Bindings(jnp.concatenate(cols, axis=0),
                    jnp.concatenate(valids, axis=0), ovf)
