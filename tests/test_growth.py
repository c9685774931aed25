"""Growth-operator correctness: packing inverses, contraction oracle,
structured-init preservation, method complexity ordering (paper Table 1),
and hypothesis property tests on the TR-MPO algebra."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig
from repro.core import baselines, grow as growlib, mango, packing
from repro.models import get_family

CFG_S = ModelConfig(name="s", n_layers=2, d_model=32, n_heads=4,
                    n_kv_heads=2, d_ff=64, vocab_size=97)
CFG_T = ModelConfig(name="t", n_layers=4, d_model=64, n_heads=8,
                    n_kv_heads=4, d_ff=128, vocab_size=97)


def _params(cfg, seed=0):
    return get_family(cfg).init(jax.random.PRNGKey(seed), cfg)


def test_pack_unpack_roundtrip():
    """unpack(pack(params)) == params when D2==D1, L2==L1, identity op."""
    params = _params(CFG_S)
    shapes = jax.eval_shape(lambda: _params(CFG_S))
    plan = packing.build_plan(CFG_S, shapes)
    g = plan.groups[0]
    M = packing.pack_group(g, params["dense_blocks"], CFG_S.d_model)
    assert M.shape[0] == len(g.slots)
    out = packing.unpack_group(g, M, shapes["dense_blocks"], CFG_S.d_model)
    for path, val in out.items():
        ref = packing._get(params["dense_blocks"], path)
        np.testing.assert_allclose(np.asarray(val, np.float32),
                                   np.asarray(ref, np.float32), atol=1e-6)


def test_contract_matches_full_mapping():
    op = mango.build_operator(CFG_S, CFG_T, rank=2)
    dims = op.dims("dense_blocks")
    cores = mango.init_cores(jax.random.PRNGKey(0), dims, 2, noise=0.05)
    M1 = jax.random.normal(jax.random.PRNGKey(1),
                           (dims["B1"], dims["I1"], dims["O1"], dims["L1"]))
    np.testing.assert_allclose(
        np.asarray(mango.contract(M1, cores)),
        np.asarray(mango.contract_reference(M1, cores)),
        rtol=3e-5, atol=3e-5)


def test_structured_init_is_net2net_like():
    """With noise=0, Mango's structured cores reproduce the bert2BERT-style
    expansion exactly (S_B=I, S_I=split, S_O=dup, S_L=layer-copy)."""
    op = mango.build_operator(CFG_S, CFG_T, rank=1)
    p_mango = mango.init_operator_params(jax.random.PRNGKey(0), op, noise=0.0)
    p_b2b = baselines.init_bert2bert_params(op, aki=False)
    small = _params(CFG_S)
    big_m = mango.grow(op, p_mango, small)
    big_b = mango.grow(op, p_b2b, small)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(big_m)[0],
            jax.tree_util.tree_flatten_with_path(big_b)[0]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5,
                                   err_msg=str(pa))


def test_net2net_width_function_preservation():
    """Width-only growth of the MLP path preserves function closely."""
    cfg_t = CFG_S.replace(name="w", d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128)
    gop, op_params = growlib.build("net2net", CFG_S, cfg_t)
    small = _params(CFG_S)
    big = growlib.grow_params(gop, op_params, small)
    fam = get_family(CFG_S)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 97)
    lo_s, _ = fam.forward(small, {"tokens": toks}, CFG_S)
    lo_b, _ = fam.forward(big, {"tokens": toks}, cfg_t)
    # logits need not match exactly (attention scale, rms over duped dims),
    # but rank correlation of predictions should be near-perfect
    ps = np.asarray(jax.nn.softmax(lo_s[:, -1]), np.float32)
    pb = np.asarray(jax.nn.softmax(lo_b[:, -1]), np.float32)
    corr = np.corrcoef(ps.ravel(), pb.ravel())[0, 1]
    assert corr > 0.9, corr


def test_operator_param_counts_table1():
    """TR-MPO core count R^2*(B1B2 + O1O2 + L1L2 + I1I2) + width matrix;
    at rank 1 this reduces to the paper's Table-1 form
    2*D1*D2 + (B1B2 + L1L2).  LiGO < Mango(rank 3); frozen methods have
    zero trainable params."""
    for rank in (1, 3):
        gop, p = growlib.build("mango", CFG_S, CFG_T, rank=rank)
        n = growlib.operator_param_count(gop, p)
        dims = gop.op.dims("dense_blocks")
        expected = rank * rank * (
            dims["B1"] * dims["B2"] + dims["L1"] * dims["L2"]
            + dims["I1"] * dims["I2"] + dims["O1"] * dims["O2"]) \
            + CFG_S.d_model * CFG_T.d_model  # + shared width matrix
        assert n == expected, (rank, n, expected)
    gop_l, p_l = growlib.build("ligo", CFG_S, CFG_T)
    n_ligo = growlib.operator_param_count(gop_l, p_l)
    gop_m1, p_m1 = growlib.build("mango", CFG_S, CFG_T, rank=1)
    assert n_ligo < growlib.operator_param_count(
        *(growlib.build("mango", CFG_S, CFG_T, rank=3)))
    for frozen in ("bert2bert", "net2net", "stackbert"):
        cfg_t = CFG_S.replace(name="d", n_layers=4) \
            if frozen == "stackbert" else CFG_T
        gop_f, p_f = growlib.build(frozen, CFG_S, cfg_t)
        assert growlib.operator_param_count(gop_f, p_f) == 0


def test_grow_is_differentiable():
    gop, op_params = growlib.build("mango", CFG_S, CFG_T, rank=1)
    small = _params(CFG_S)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 97)
    fam = get_family(CFG_T)

    def loss(p):
        big = growlib.grow_params(gop, p, small)
        logits, _ = fam.forward(big, {"tokens": toks}, CFG_T)
        return jnp.mean(jnp.square(logits.astype(jnp.float32)))

    g = jax.grad(loss)(op_params)
    gnorm = sum(float(jnp.sum(jnp.abs(x)))
                for x in jax.tree.leaves(g["groups"]))
    assert np.isfinite(gnorm) and gnorm > 0


# --------------------------------------------------------- property tests
@settings(max_examples=20, deadline=None)
@given(
    b1=st.integers(2, 5), l1=st.integers(1, 3), i1=st.integers(2, 6),
    o1=st.integers(2, 6), rank=st.integers(1, 3), scale=st.floats(0.5, 2.0),
)
def test_contract_linearity_property(b1, l1, i1, o1, rank, scale):
    """The growth map is linear in M1: Φ(aM) = aΦ(M); Φ(M+N) = Φ(M)+Φ(N)."""
    dims = {"B1": b1, "B2": b1 + 1, "I1": i1, "I2": i1 + 2,
            "O1": o1, "O2": o1 + 1, "L1": l1, "L2": l1 + 1}
    cores = mango.init_cores(jax.random.PRNGKey(0), dims, rank, noise=0.1)
    key = jax.random.PRNGKey(b1 * 100 + o1)
    M = jax.random.normal(key, (b1, i1, o1, l1))
    N = jax.random.normal(jax.random.PRNGKey(7), (b1, i1, o1, l1))
    a = jnp.float32(scale)
    np.testing.assert_allclose(
        np.asarray(mango.contract(a * M, cores)),
        np.asarray(a * mango.contract(M, cores)), rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(mango.contract(M + N, cores)),
        np.asarray(mango.contract(M, cores)
                   + mango.contract(N, cores)), rtol=2e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(d1=st.sampled_from([16, 32]), mult=st.integers(1, 3))
def test_width_expand_preserves_rowspace(d1, mult):
    """Split/dup width maps compose to identity: dup @ split^T == I."""
    d2 = d1 * mult
    split = mango.width_expand_matrix(d1, d2, normalized=True)
    dup = mango.width_expand_matrix(d1, d2, normalized=False)
    np.testing.assert_allclose(np.asarray(dup @ split.T), np.eye(d1),
                               atol=1e-6)
