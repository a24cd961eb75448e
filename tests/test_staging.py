"""Stage packing: exactness, conflict-freedom, depth bounds, and anytime
prefix-cut semantics (DESIGN.md §9)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (approximate_symmetric, approximate_general,
                        g_to_dense, t_to_dense, pack_g, pack_g_adjoint,
                        pack_t, pack_t_inverse)
from repro.core.staging import pack_g_batch, pack_t_batch, select_cut
from repro.core.types import GFactors, TFactors
from repro.kernels import ref
from repro.tables import truncate_staged


def _sym(n, seed):
    x = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    return jnp.asarray(x + x.T)


def test_staged_g_equals_sequential():
    n = 20
    f, _, _ = approximate_symmetric(_sym(n, 0), g=50, n_iter=1)
    u = np.asarray(g_to_dense(f, n))
    staged = pack_g(f)
    x = np.random.default_rng(1).standard_normal((7, n)).astype(np.float32)
    y = ref.staged_g_apply(staged, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), x @ u.T, atol=2e-5)


def test_staged_g_adjoint():
    n = 16
    f, _, _ = approximate_symmetric(_sym(n, 2), g=30, n_iter=1)
    u = np.asarray(g_to_dense(f, n))
    adj = pack_g_adjoint(f)
    x = np.random.default_rng(3).standard_normal((4, n)).astype(np.float32)
    y = ref.staged_g_apply(adj, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), x @ u, atol=2e-5)


@pytest.mark.slow
def test_staged_t_forward_and_inverse():
    n = 14
    c = jnp.asarray(np.random.default_rng(4).standard_normal(
        (n, n)).astype(np.float32))
    f, _, _ = approximate_general(c, m=25, n_iter=1)
    t = np.asarray(t_to_dense(f, n))
    fwd = pack_t(f, n)
    inv = pack_t_inverse(f, n)
    x = np.random.default_rng(5).standard_normal((6, n)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(ref.staged_t_apply(fwd, jnp.asarray(x))), x @ t.T,
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(ref.staged_t_apply(inv, jnp.asarray(x))),
        x @ np.linalg.inv(t).T, rtol=1e-3, atol=1e-3)


def test_stages_conflict_free():
    n = 24
    f, _, _ = approximate_symmetric(_sym(n, 6), g=60, n_iter=1)
    st = pack_g(f)
    ii = np.asarray(st.idx_i)
    jj = np.asarray(st.idx_j)
    for s in range(st.num_stages):
        touched = []
        for a, b in zip(ii[s], jj[s]):
            if a == b:       # padding no-op
                continue
            touched.extend([a, b])
        assert len(touched) == len(set(touched)), f"conflict in stage {s}"


def test_stage_depth_compresses_chain():
    """Greedy packing must expose real parallelism: the O(g)-deep
    sequential chain packs into <= g/4 stages (measured ~g/6 for
    Theorem-1 chains at n=64; greedy pair selection concentrates on hot
    coordinates so the ideal n/2-wide stages are not reachable)."""
    n = 64
    alpha = 2
    g = alpha * n * int(np.log2(n))
    f, _, _ = approximate_symmetric(_sym(n, 7), g=g, n_iter=0)
    st = pack_g(f)
    assert st.num_stages <= g // 4, (st.num_stages, g)


def test_sym_operator_matches_dense():
    n = 18
    s = _sym(n, 8)
    f, sbar, _ = approximate_symmetric(s, g=40, n_iter=2)
    u = np.asarray(g_to_dense(f, n))
    sbar_np = np.asarray(sbar)
    dense_op = u @ np.diag(sbar_np) @ u.T
    x = np.random.default_rng(9).standard_normal((5, n)).astype(np.float32)
    y = ref.sym_operator_apply(pack_g(f), pack_g_adjoint(f),
                               jnp.asarray(sbar_np), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), x @ dense_op.T,
                               rtol=1e-3, atol=1e-3)


def test_gen_operator_matches_dense():
    n = 12
    c = jnp.asarray(np.random.default_rng(10).standard_normal(
        (n, n)).astype(np.float32))
    f, cbar, _ = approximate_general(c, m=20, n_iter=2)
    t = np.asarray(t_to_dense(f, n))
    dense_op = t @ np.diag(np.asarray(cbar)) @ np.linalg.inv(t)
    x = np.random.default_rng(11).standard_normal((5, n)).astype(np.float32)
    y = ref.gen_operator_apply(pack_t(f, n), pack_t_inverse(f, n),
                               cbar, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), x @ dense_op.T,
                               rtol=1e-2, atol=1e-2)


# ---------------------------------------------------------------------------
# Anytime prefix cuts (DESIGN.md §9): truncating the staged tables at a
# recorded boundary must equal sequentially applying the leading g'
# fundamental components — for the G family those are the application-order
# TAIL factors (discovery order is reversed application order), for the T
# family the application-order HEAD.
# ---------------------------------------------------------------------------


def _g_prefix(f, k):
    g = f.g
    return GFactors(*(arr[g - k:] for arr in f))


def _t_prefix(f, k):
    return TFactors(*(arr[:k] for arr in f))


def test_prefix_cut_g_matches_factor_prefix():
    n, g = 20, 50
    f, _, _ = approximate_symmetric(_sym(n, 12), g=g, n_iter=1)
    fwd = pack_g(f)
    adj = pack_g_adjoint(f)
    x = np.random.default_rng(13).standard_normal((6, n)).astype(np.float32)
    assert fwd.cuts is not None and fwd.cuts[-1].tolist() == [
        fwd.num_stages, g]
    np.testing.assert_array_equal(np.asarray(fwd.cuts),
                                  np.asarray(adj.cuts))
    for s, k in fwd.cuts:
        up = (np.asarray(g_to_dense(_g_prefix(f, int(k)), n)) if k
              else np.eye(n, dtype=np.float32))
        # forward (synthesis) tables: significant stages at the TAIL
        yt = ref.staged_g_apply(fwd, jnp.asarray(x), num_stages=int(s),
                                keep="tail")
        np.testing.assert_allclose(np.asarray(yt), x @ up.T, atol=2e-5)
        # adjoint (analysis) tables: mirrored, significant at the HEAD
        yh = ref.staged_g_apply(adj, jnp.asarray(x), num_stages=int(s),
                                keep="head")
        np.testing.assert_allclose(np.asarray(yh), x @ up, atol=2e-5)


@pytest.mark.slow
def test_prefix_cut_t_matches_factor_prefix():
    n, m = 14, 25
    c = jnp.asarray(np.random.default_rng(14).standard_normal(
        (n, n)).astype(np.float32))
    f, _, _ = approximate_general(c, m=m, n_iter=1)
    fwd = pack_t(f, n)
    inv = pack_t_inverse(f, n)
    x = np.random.default_rng(15).standard_normal((5, n)).astype(np.float32)
    for s, k in fwd.cuts:
        tp = (np.asarray(t_to_dense(_t_prefix(f, int(k)), n)) if k
              else np.eye(n, dtype=np.float32))
        yt = ref.staged_t_apply(fwd, jnp.asarray(x), num_stages=int(s),
                                keep="head")
        np.testing.assert_allclose(np.asarray(yt), x @ tp.T,
                                   rtol=1e-4, atol=1e-4)
        yi = ref.staged_t_apply(inv, jnp.asarray(x), num_stages=int(s),
                                keep="tail")
        np.testing.assert_allclose(np.asarray(yi),
                                   x @ np.linalg.inv(tp).T,
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_prefix_cut_batched_g_and_t():
    """Batched (B, S, P) tables: chunk-uniform padding keeps every cut at
    the SAME stage index for all matrices, so one static num_stages cuts
    the whole batch exactly."""
    b, n, g = 3, 16, 40
    fs = [approximate_symmetric(_sym(n, 20 + i), g=g, n_iter=1)[0]
          for i in range(b)]
    fb = GFactors(*(jnp.stack([getattr(fs[i], fld) for i in range(b)])
                    for fld in GFactors._fields))
    fwd = pack_g_batch(fb, n)
    adj = pack_g_batch(fb, n, adjoint=True)
    x = jnp.asarray(np.random.default_rng(21).standard_normal(
        (b, 4, n)).astype(np.float32))
    for s, k in fwd.cuts:
        yt = ref.batched_g_apply(fwd, x, num_stages=int(s), keep="tail")
        yh = ref.batched_g_apply(adj, x, num_stages=int(s), keep="head")
        for i in range(b):
            up = (np.asarray(g_to_dense(_g_prefix(fs[i], int(k)), n)) if k
                  else np.eye(n, dtype=np.float32))
            np.testing.assert_allclose(np.asarray(yt[i]),
                                       np.asarray(x[i]) @ up.T, atol=3e-5)
            np.testing.assert_allclose(np.asarray(yh[i]),
                                       np.asarray(x[i]) @ up, atol=3e-5)

    m = 30
    cs = [jnp.asarray(np.random.default_rng(30 + i).standard_normal(
        (n, n)).astype(np.float32)) for i in range(b)]
    ts = [approximate_general(cs[i], m=m, n_iter=1)[0] for i in range(b)]
    tb = TFactors(*(jnp.stack([getattr(ts[i], fld) for i in range(b)])
                    for fld in TFactors._fields))
    tfwd = pack_t_batch(tb, n)
    tinv = pack_t_batch(tb, n, inverse=True)
    s, k = select_cut(tfwd, fraction=0.5)
    yt = ref.batched_t_apply(tfwd, x, num_stages=s, keep="head")
    yi = ref.batched_t_apply(tinv, x, num_stages=s, keep="tail")
    for i in range(b):
        tp = np.asarray(t_to_dense(_t_prefix(ts[i], k), n))
        np.testing.assert_allclose(np.asarray(yt[i]),
                                   np.asarray(x[i]) @ tp.T,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(yi[i]),
                                   np.asarray(x[i]) @ np.linalg.inv(tp).T,
                                   rtol=1e-3, atol=1e-3)


def test_adjoint_is_stage_mirror():
    """pack_g_adjoint must be the exact stage-mirror of pack_g (reversed
    stage order, rotations flip s) — THE invariant that makes one
    num_stages cut both directions consistently."""
    n = 18
    f, _, _ = approximate_symmetric(_sym(n, 40), g=36, n_iter=1)
    fwd = pack_g(f)
    adj = pack_g_adjoint(f)
    np.testing.assert_array_equal(np.asarray(fwd.idx_i)[::-1],
                                  np.asarray(adj.idx_i))
    np.testing.assert_array_equal(np.asarray(fwd.idx_j)[::-1],
                                  np.asarray(adj.idx_j))
    sg = np.asarray(fwd.sigma)[::-1]
    s_mirror = np.where(sg > 0, -np.asarray(fwd.s)[::-1],
                        np.asarray(fwd.s)[::-1])
    np.testing.assert_array_equal(np.asarray(adj.s), s_mirror)


def test_truncate_staged_validates_and_trims_cuts():
    import pytest
    n = 16
    f, _, _ = approximate_symmetric(_sym(n, 50), g=32, n_iter=0)
    st = pack_g(f)
    with pytest.raises(ValueError):
        truncate_staged(st, st.num_stages + 1)
    with pytest.raises(ValueError):
        truncate_staged(st, 1, keep="middle")
    s, k = select_cut(st, fraction=0.5)
    cut = truncate_staged(st, s, keep="tail")
    assert cut.num_stages == s
    assert int(np.asarray(cut.cuts)[:, 0].max()) <= s
    assert truncate_staged(st, None) is st
