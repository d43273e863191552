"""Per-kernel fidelity: Pallas (interpret mode) vs pure-jnp oracle, swept
over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kb import kb_from_triples
from repro.core.pattern import Bindings, CompiledPattern, Slot
from repro.core.rdf import Vocab

from repro.kernels.hash_join import ops as hj_ops
from repro.kernels.hash_join.ref import match_matrix_ref
from repro.kernels.closure import ops as cl_ops
from repro.kernels.closure.ref import closure_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd.ref import ssd_ref


# --------------------------------------------------------------------------
# hash_join
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,nv", [(16, 64, 2), (64, 256, 3), (128, 512, 4)])
@pytest.mark.parametrize("pat_kind", ["bound_const_free", "free_const_bound", "const_bound_free"])
def test_hash_join_matches_ref(m, n, nv, pat_kind):
    rng = np.random.default_rng(m * n + len(pat_kind))
    base = 5000
    cols = rng.integers(base, base + 30, size=(m, nv)).astype(np.uint32)
    bvalid = rng.random(m) < 0.9
    rows = [
        (int(rng.integers(base, base + 30)), int(rng.integers(1, 4)),
         int(rng.integers(base, base + 30)))
        for _ in range(n - 4)
    ]
    kb = kb_from_triples(rows, capacity=n)
    if pat_kind == "bound_const_free":
        pat = CompiledPattern(Slot.bound(0), Slot.const_(2), Slot.free(1))
    elif pat_kind == "free_const_bound":
        pat = CompiledPattern(Slot.free(0), Slot.const_(1), Slot.bound(1))
    else:
        pat = CompiledPattern(Slot.const_(base + 3), Slot.bound(0), Slot.free(1))

    bind = Bindings(jnp.asarray(cols), jnp.asarray(bvalid), jnp.zeros((), bool))
    got = hj_ops.match_matrix(bind, kb, pat)
    want = match_matrix_ref(
        bind.cols, bind.valid, kb.s_ps, kb.p_ps, kb.o_ps, kb.valid, pat
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hash_join_repeated_var():
    rng = np.random.default_rng(0)
    rows = [(10_000 + i % 3, 1, 10_000 + i % 2) for i in range(12)]
    kb = kb_from_triples(rows, capacity=16)
    cols = rng.integers(9_999, 10_004, size=(8, 2)).astype(np.uint32)
    bind = Bindings(jnp.asarray(cols), jnp.ones((8,), bool), jnp.zeros((), bool))
    pat = CompiledPattern(Slot.free(0), Slot.const_(1), Slot.free(0))  # ?x p ?x
    got = hj_ops.match_matrix(bind, kb, pat)
    want = match_matrix_ref(bind.cols, bind.valid, kb.s_ps, kb.p_ps, kb.o_ps,
                            kb.valid, pat)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# closure
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 64, 130, 256])
def test_closure_matches_ref(n):
    rng = np.random.default_rng(n)
    adj = (rng.random((n, n)) < 0.05).astype(np.float32)
    got = cl_ops.transitive_closure(jnp.asarray(adj), max_depth=n, use_pallas=True)
    want = closure_ref(jnp.asarray(adj), steps=int(np.ceil(np.log2(max(2, n)))))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want) > 0.5)


def test_closure_chain_depth():
    # a chain 0 -> 1 -> 2 -> ... -> 9: closure must connect 0 to 9
    n = 10
    adj = np.zeros((n, n), np.float32)
    for i in range(n - 1):
        adj[i, i + 1] = 1.0
    reach = np.asarray(cl_ops.transitive_closure(jnp.asarray(adj), max_depth=n))
    assert reach[0, 9] and reach[0, 0] and not reach[9, 0]


def test_closure_cycle_reaches_everything():
    """A directed cycle: every node reaches every node; the squaring
    fixpoint must saturate, not loop or overshoot."""
    n = 6
    adj = np.zeros((n, n), np.float32)
    for i in range(n):
        adj[i, (i + 1) % n] = 1.0
    reach = np.asarray(cl_ops.transitive_closure(jnp.asarray(adj), max_depth=n))
    assert reach.all()
    ids, count = cl_ops.closure_descendants(jnp.asarray(adj), root=2,
                                            out_cap=n, max_depth=n)
    assert int(count) == n
    np.testing.assert_array_equal(np.asarray(ids), np.arange(n))


def test_closure_descendants_empty_and_isolated_root():
    """Zero-edge adjacency: the closure is reflexive only — every root's
    descendant set is exactly itself."""
    for n in (1, 8):
        adj = np.zeros((n, n), np.float32)
        ids, count = cl_ops.closure_descendants(jnp.asarray(adj), root=0,
                                                out_cap=max(n, 2),
                                                max_depth=n)
        assert int(count) == 1
        assert int(np.asarray(ids)[0]) == 0


def test_closure_ancestors_is_transposed_descendants():
    rng = np.random.default_rng(3)
    n = 24
    adj = (rng.random((n, n)) < 0.12).astype(np.float32)
    for root in (0, 5, 17):
        a_ids, a_count = cl_ops.closure_ancestors(
            jnp.asarray(adj), root=root, out_cap=n, max_depth=n)
        d_ids, d_count = cl_ops.closure_descendants(
            jnp.asarray(adj.T), root=root, out_cap=n, max_depth=n)
        assert int(a_count) == int(d_count)
        np.testing.assert_array_equal(np.asarray(a_ids), np.asarray(d_ids))
        # oracle: rows the root reaches in the closure matrix
        reach = np.asarray(cl_ops.transitive_closure(
            jnp.asarray(adj), max_depth=n, use_pallas=False))
        want = np.nonzero(reach[root])[0]
        np.testing.assert_array_equal(
            np.sort(np.asarray(a_ids)[: int(a_count)]), want)


def test_closure_ops_interpret_parity():
    """interpret=True (Pallas interpreter) and interpret=False (compiled)
    must agree bit-for-bit; compiled mode needs a TPU, so the pair only runs
    on one.  A compile failure there fails the test."""
    if jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas kernels need a TPU backend")
    rng = np.random.default_rng(11)
    n = 8
    adj = (rng.random((n, n)) < 0.2).astype(np.float32)
    i_ids, i_count = cl_ops.closure_descendants(
        jnp.asarray(adj), root=1, out_cap=n, max_depth=n, interpret=True)
    c_ids, c_count = jax.block_until_ready(cl_ops.closure_descendants(
        jnp.asarray(adj), root=1, out_cap=n, max_depth=n, interpret=False))
    np.testing.assert_array_equal(np.asarray(i_ids), np.asarray(c_ids))
    assert int(i_count) == int(c_count)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

ATT_SHAPES = [
    # (b, hq, hk, tq, tk, d)
    (1, 2, 2, 128, 128, 64),      # MHA
    (1, 4, 2, 128, 128, 64),      # GQA 2:1
    (2, 8, 1, 128, 128, 32),      # MQA
    (1, 2, 2, 256, 256, 128),     # multi-block
]


@pytest.mark.parametrize("shape", ATT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(shape, dtype):
    b, hq, hk, tq, tk, d = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    q = jnp.asarray(rng.standard_normal((b, hq, tq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hk, tk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hk, tk, d)), dtype)
    got = fa_ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_sliding_window():
    b, hq, hk, t, d = 1, 2, 2, 256, 64
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, hq, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, t, d)), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, causal=True, window=64, bq=64, bk=64)
    want = attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_offset():
    """Decode shape: one query position attending over a long KV cache."""
    b, hq, hk, tk, d = 2, 4, 2, 512, 64
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((b, hq, 8, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, tk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, tk, d)), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, causal=True, q_offset=tk - 8, bq=8, bk=128)
    want = attention_ref(q, k, v, causal=True, q_offset=tk - 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# ssd (Mamba-2)
# --------------------------------------------------------------------------

SSD_SHAPES = [
    # (b, t, h, p, g, s, chunk)
    (1, 64, 2, 16, 1, 16, 32),
    (2, 128, 4, 32, 2, 32, 64),
    (1, 96, 2, 64, 1, 128, 32),    # t not multiple of default chunk
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_matches_ref(shape):
    b, t, h, p, g, s, chunk = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, t, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, t, g, s)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, t, g, s)), jnp.float32)
    D = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    got = ssd_ops.ssd(x, dt, A, Bm, Cm, D, chunk=chunk, use_pallas=True)
    want, _ = ssd_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_ssd_final_state_matches_ref():
    b, t, h, p, g, s, chunk = 1, 64, 2, 16, 1, 16, 16
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, t, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, t, g, s)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, t, g, s)), jnp.float32)
    from repro.kernels.ssd.kernel import ssd_pallas
    _, state_k = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk)
    _, state_r = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(state_k), np.asarray(state_r),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# engine integration: scan-method join through the Pallas kernel
# --------------------------------------------------------------------------

def test_engine_kb_join_pallas_path():
    from repro.core import algebra
    rng = np.random.default_rng(5)
    rows = [(int(rng.integers(8000, 8010)), int(rng.integers(1, 3)),
             int(rng.integers(8000, 8010))) for _ in range(40)]
    kb = kb_from_triples(rows, capacity=64)
    cols = rng.integers(8000, 8010, size=(16, 2)).astype(np.uint32)
    bind = Bindings(jnp.asarray(cols), jnp.ones((16,), bool), jnp.zeros((), bool))
    pat = CompiledPattern(Slot.bound(0), Slot.const_(1), Slot.free(1))
    out_pallas = algebra.kb_join_scan(bind, kb, pat, out_cap=128, use_pallas=True)
    out_jnp = algebra.kb_join_scan(bind, kb, pat, out_cap=128, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(out_pallas.cols), np.asarray(out_jnp.cols))
    np.testing.assert_array_equal(np.asarray(out_pallas.valid), np.asarray(out_jnp.valid))
