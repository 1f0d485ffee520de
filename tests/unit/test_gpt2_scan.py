"""`scan_layers` compile collapse (`deepspeed_tpu/models/gpt2.py`).

Stacking the transformer Blocks into one `lax.scan` trades N copies of
the layer program for one while-loop body: the pins here are the two
halves of that trade. Numerics: scan-vs-unrolled is bit-exact on loss
AND grads at 12 layers under remat (jax.checkpoint's barriers isolate
each block's fusion identically in both programs; without remat XLA
fuses across unrolled layers and grads agree only to float tolerance —
loss stays bit-exact either way). Compile: the lowered-HLO size must
drop by a pinned ratio (measured ~0.34x on CPU, and ~0.15x the wall;
the size is pinned loosely at 0.7).

Plus the checkpoint-compat converters: stacked <-> per-layer param
pytrees round-trip bit-exactly, and a scan model's params load into the
unrolled model (and back) with identical loss.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.gpt2 import (
    GPT2Config,
    GPT2LMHead,
    gpt2_partition_specs,
    init_gpt2_params,
    make_gpt2_loss_fn,
    stack_gpt2_layer_params,
    unstack_gpt2_layer_params,
)

N_LAYER = 12


def _cfg(scan_layers, **kw):
    # f32 compute: the bit-exactness pins hold at full precision (bf16
    # keeps f32 intermediates inside XLA fusions and rounds at
    # different points in the two programs).
    kw.setdefault("dropout", 0.0)
    kw.setdefault("dtype", jnp.float32)
    return GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                      n_layer=N_LAYER, n_head=4,
                      scan_layers=scan_layers, **kw)


def _loss_and_grads(cfg, params, batch):
    model = GPT2LMHead(cfg)
    loss_fn = make_gpt2_loss_fn(model)

    @jax.jit
    def step(p):
        return jax.value_and_grad(
            lambda q: loss_fn(q, batch, jax.random.PRNGKey(1)))(p)

    return step(params)


def _batch(rows=4, seq=16):
    rng = np.random.default_rng(0)
    return {"input_ids": rng.integers(0, 255, (rows, seq))
            .astype(np.int32)}


def _stacked_params(cfg_scan, cfg_unrolled):
    """Identical weights in both layouts: init the unrolled model, stack
    its layers for the scan model."""
    unrolled = init_gpt2_params(GPT2LMHead(cfg_unrolled),
                                jax.random.PRNGKey(0))
    return unrolled, stack_gpt2_layer_params(unrolled)


def _assert_within_one_ulp(a, b):
    """Equal to the last float32 bit or the one beside it: XLA (jax
    0.9.0) rounds the scan's and the unrolled program's final loss
    reduction one ulp apart (5.5682487 vs 5.568249)."""
    a, b = np.float32(a), np.float32(b)
    assert abs(a - b) <= np.spacing(max(abs(a), abs(b))), (a, b)


def _assert_trees_bitexact(a, b):
    leaves_a = jax.tree_util.tree_leaves_with_path(a)
    leaves_b = dict(jax.tree_util.tree_leaves_with_path(b))
    assert len(leaves_a) == len(leaves_b)
    for path, leaf in leaves_a:
        other = leaves_b[path]
        assert np.array_equal(np.asarray(leaf), np.asarray(other)), \
            f"mismatch at {jax.tree_util.keystr(path)}"


def _assert_grads_within_leaf_ulps(a, b, ulps=8):
    """Every grad leaf equal to within ``ulps`` float32 epsilons of the
    leaf's own largest magnitude. Under jax 0.9.0's XLA the scan and the
    unrolled backward accumulate in a different order, so the leaves are
    no longer bit-identical; measured worst leaf (wpe) 6.1 eps, so 8 is
    the power of two that holds. An elementwise ulp bound cannot: the
    difference is absolute (~1e-9), and near-zero entries make it
    thousands of their own ulps."""
    leaves_a = jax.tree_util.tree_leaves_with_path(a)
    leaves_b = dict(jax.tree_util.tree_leaves_with_path(b))
    assert len(leaves_a) == len(leaves_b)
    eps = np.finfo(np.float32).eps
    for path, leaf in leaves_a:
        x = np.asarray(leaf, np.float32)
        y = np.asarray(leaves_b[path], np.float32)
        bound = ulps * eps * max(np.abs(x).max(), np.abs(y).max())
        assert np.abs(x - y).max() <= bound, \
            f"mismatch at {jax.tree_util.keystr(path)}"


# ---------------------------------------------------------------------------
# numerics parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [
    "full",
    pytest.param("dots", marks=pytest.mark.slow),
])
def test_scan_matches_unrolled_loss_and_grads_under_remat(policy):
    """The acceptance pin: 12-layer scan vs unrolled, remat on — loss
    within one float32 ulp and every grad leaf within 8 eps of its own
    scale (bit-identical until jax 0.9.0's XLA reordered the sums)."""
    cfg_u = _cfg(False, remat=True, remat_policy=policy)
    cfg_s = _cfg(True, remat=True, remat_policy=policy)
    batch = _batch()
    params_u, params_s = _stacked_params(cfg_s, cfg_u)
    loss_u, grads_u = _loss_and_grads(cfg_u, params_u, batch)
    loss_s, grads_s = _loss_and_grads(cfg_s, params_s, batch)
    _assert_within_one_ulp(loss_u, loss_s)
    _assert_grads_within_leaf_ulps(stack_gpt2_layer_params(grads_u),
                                   grads_s)


@pytest.mark.slow
def test_scan_parity_without_remat():
    """No remat: loss within one ulp; grads agree to float32 tolerance
    (XLA fuses across unrolled layers, reordering last-ulp rounding)."""
    cfg_u, cfg_s = _cfg(False), _cfg(True)
    batch = _batch()
    params_u, params_s = _stacked_params(cfg_s, cfg_u)
    loss_u, grads_u = _loss_and_grads(cfg_u, params_u, batch)
    loss_s, grads_s = _loss_and_grads(cfg_s, params_s, batch)
    _assert_within_one_ulp(loss_u, loss_s)
    stacked_u = stack_gpt2_layer_params(grads_u)
    for path, leaf in jax.tree_util.tree_leaves_with_path(stacked_u):
        other = dict(jax.tree_util.tree_leaves_with_path(grads_s))[path]
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(other),
                                   rtol=0, atol=1e-5)


def test_scan_pld_and_dropout_still_run():
    """The PLD skip under scan uses a multiplicative gate instead of
    lax.cond (flax submodules cannot be built inside a lifted-scan
    branch); make sure that path traces and differentiates."""
    cfg = _cfg(True, dropout=0.1)
    model = GPT2LMHead(cfg)
    params = init_gpt2_params(model, jax.random.PRNGKey(0))
    loss_fn = make_gpt2_loss_fn(model)
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, _batch(), jax.random.PRNGKey(1),
                          pld_theta=0.5))(params)
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))


# ---------------------------------------------------------------------------
# compile collapse (the pinned ratios)
# ---------------------------------------------------------------------------

@pytest.mark.full_compile
def test_scan_cuts_compile_wall_and_hlo_size():
    """Measured on CPU at 12 layers: ~0.34x HLO chars (and ~0.15x the
    compile wall, which is what the scan is for). The size is what
    fails if the scan ever silently unrolls, and is pinned loosely
    (0.7); the wall is not asserted: beside five other test workers one
    compile can take several times its own time."""
    batch = _batch()
    chars = {}
    for name, scan in (("unrolled", False), ("scan", True)):
        model = GPT2LMHead(_cfg(scan))
        params = init_gpt2_params(model, jax.random.PRNGKey(0))
        loss_fn = make_gpt2_loss_fn(model)

        def step(p):
            return jax.value_and_grad(
                lambda q: loss_fn(q, batch, jax.random.PRNGKey(1)))(p)

        chars[name] = len(jax.jit(step).lower(params).compile().as_text())
    assert chars["scan"] / chars["unrolled"] < 0.7, chars


# ---------------------------------------------------------------------------
# converters + specs
# ---------------------------------------------------------------------------

def test_stack_unstack_roundtrip_bitexact():
    cfg_u, cfg_s = _cfg(False), _cfg(True)
    params_u = init_gpt2_params(GPT2LMHead(cfg_u), jax.random.PRNGKey(0))
    stacked = stack_gpt2_layer_params(params_u)
    # structure matches a natively-initialized scan model
    native = init_gpt2_params(GPT2LMHead(cfg_s), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(stacked) == \
        jax.tree_util.tree_structure(native)
    # and the round trip is bit-identical
    _assert_trees_bitexact(unstack_gpt2_layer_params(stacked), params_u)


@pytest.mark.slow
def test_converted_params_give_identical_loss_across_layouts():
    cfg_u, cfg_s = _cfg(False), _cfg(True)
    batch = _batch()
    params_s = init_gpt2_params(GPT2LMHead(cfg_s), jax.random.PRNGKey(0))
    loss_s, _ = _loss_and_grads(cfg_s, params_s, batch)
    loss_u, _ = _loss_and_grads(
        cfg_u, unstack_gpt2_layer_params(params_s), batch)
    assert float(loss_s) == float(loss_u)


def test_converter_error_cases():
    with pytest.raises(ValueError, match="h_<i>"):
        stack_gpt2_layer_params({"wte": np.zeros((4, 4))})
    with pytest.raises(ValueError, match="non-contiguous"):
        stack_gpt2_layer_params({"h_0": {"w": np.zeros(3)},
                                 "h_2": {"w": np.zeros(3)}})
    with pytest.raises(ValueError, match="stacked"):
        unstack_gpt2_layer_params({"wte": np.zeros((4, 4))})


def test_partition_specs_prepend_layer_axis_for_stacked():
    cfg_s = _cfg(True)
    params = init_gpt2_params(GPT2LMHead(cfg_s), jax.random.PRNGKey(0))
    specs = gpt2_partition_specs(params)
    flat = {jax.tree_util.keystr(path): spec for path, spec in
            jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))}
    attn_keys = [k for k in flat if "['h']" in k and "attn" in k
                 and "kernel" in k]
    assert attn_keys
    for key in attn_keys:
        spec = flat[key]
        # leading layer axis replicated, original spec shifted right
        assert spec[0] is None
        assert "model" in tuple(spec)
