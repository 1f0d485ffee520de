"""`models/blocks.py`: what the served decoders share, held directly.

The copies these parts replaced were held only through each model's own
tests; here the rule that keeps them from growing back (no served model
imports a sibling), the experts' counters against a count made by hand,
and the centring of a writer.
"""

import ast
import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import blocks

SERVED = ("granite_hybrid", "mla_moe", "nemotron_h", "qwen3_next", "mimo_v2",
          "laguna", "ling_hybrid", "lfm2_moe")


@pytest.mark.parametrize("name", SERVED)
def test_a_served_model_imports_no_sibling_model(name):
    """Of `deepspeed_tpu.models` a served model's source imports
    `blocks` and nothing else, wherever in the file the import stands."""
    module = importlib.import_module(f"deepspeed_tpu.models.{name}")
    with open(module.__file__) as f:
        tree = ast.parse(f.read())
    siblings = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "deepspeed_tpu.models":
                siblings.update(a.name for a in node.names)
            elif node.module.startswith("deepspeed_tpu.models."):
                siblings.add(node.module.split(".")[2])
        elif isinstance(node, ast.Import):
            siblings.update(a.name.split(".")[2] for a in node.names
                            if a.name.startswith("deepspeed_tpu.models."))
    assert siblings == {"blocks"}, (name, siblings)
    # and the protocol's model side is the mixin's
    lm = next(v for k, v in vars(module).items() if k.endswith("LM"))
    assert issubclass(lm, blocks.ServedLM)
    assert lm.serve_apply is blocks.ServedLM.serve_apply
    assert lm.cache_spec is blocks.ServedLM.cache_spec


def test_expert_counters_by_name_against_a_count_by_hand():
    """Six tokens of which one is masked, four experts of which this
    share holds experts 1 and 2, top 2; the choice is written into the
    router so that the count can be made by hand."""
    from deepspeed_tpu.moe.dropless import dropless_moe, softmax_top_k
    top_k, first, held, E, M, I = 2, 1, 2, 4, 4, 8
    # token i's two experts (one-hot inputs, so the router's row is the
    # token's logits): the masked token 3 would have sent both here
    chosen = [(0, 1), (1, 2), (2, 3), (1, 2), (0, 3), (1, 3)]
    mask = jnp.asarray([True, True, True, False, True, True])
    logits = np.full((len(chosen), E), -9.0, np.float32)
    for i, pair in enumerate(chosen):
        logits[i, list(pair)] = 9.0
    x = jnp.eye(len(chosen), M + 2, dtype=jnp.float32)      # [6, 6]
    router = jnp.asarray(logits)                            # [6, E]
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    w_gate, w_up = (jax.random.normal(k, (held, M + 2, I)) for k in key[:2])
    w_down = jax.random.normal(key[2], (held, I, M + 2))
    _, stats = dropless_moe(x, router, w_gate, w_up, w_down, top_k,
                            route=softmax_top_k, first_expert=first,
                            token_mask=mask)
    got = blocks.expert_counters(mask, top_k, stats)
    live = [pair for pair, m in zip(chosen, np.asarray(mask)) if m]
    here = [e for pair in live for e in pair if first <= e < first + held]
    per_expert = [here.count(e) for e in range(first, first + held)]
    assert per_expert == [3, 2]             # the count by hand
    assert int(got.pairs_routed) == 5 * top_k
    assert int(got.pairs_held) == 5
    assert int(got.experts_touched) == 2
    assert int(got.pairs_max) == 3
    tile = math.gcd(len(chosen) * top_k, 256)       # `moe/dropless.py`'s
    assert int(got.rows_visited) == -(-5 // tile) * tile
    assert got._fields == ("pairs_routed", "pairs_held", "experts_touched",
                           "pairs_max", "rows_visited")

    # a model's values from its layers' counters: sums, the one largest,
    # what the model passes, and zeros where no layer counts
    other = got._replace(pairs_max=jnp.int32(7), pairs_held=jnp.int32(1))
    names = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs_max",
             "moe_experts_held", "rows_live", "moe_rows_visited")
    summed = blocks.summed_counters(
        names, [got, other], moe_experts_held=jnp.int32(4), rows_live=5)
    assert tuple(summed) == names
    assert {k: int(v) for k, v in summed.items()} == {
        "moe_pairs_routed": 20, "moe_pairs_held": 6, "moe_pairs_max": 7,
        "moe_experts_held": 4, "rows_live": 5,
        "moe_rows_visited": 2 * int(got.rows_visited)}
    none = blocks.summed_counters(names[:3], [])
    assert {k: int(v) for k, v in none.items()} == dict.fromkeys(names[:3], 0)


def test_centred_moves_the_writers_and_nothing_else():
    key = jax.random.split(jax.random.PRNGKey(1), 4)
    params = {"layers_0": {
        "attn": {"q_proj": 1.0 + jax.random.normal(key[0], (8, 6)),
                 "o_proj": 1.0 + jax.random.normal(key[1], (6, 8))},
        "experts": {"w_down": (1.0 + jax.random.normal(
            key[2], (3, 5, 8))).astype(jnp.bfloat16)},
        "norm": {"weight": jnp.ones((8,))}}}
    got = blocks.centred(params, {"o_proj": 0, "w_down": 1})
    layer = got["layers_0"]
    # a non-writer to the bit, a writer's mean over its input axis gone
    np.testing.assert_array_equal(layer["attn"]["q_proj"],
                                  params["layers_0"]["attn"]["q_proj"])
    np.testing.assert_array_equal(layer["norm"]["weight"], 1.0)
    assert np.abs(np.asarray(layer["attn"]["o_proj"]).mean(0)).max() < 1e-6
    assert np.abs(np.asarray(params["layers_0"]["attn"]["o_proj"]
                             ).mean(0)).max() > 0.1
    down = layer["experts"]["w_down"]
    assert down.dtype == jnp.bfloat16 and down.shape == (3, 5, 8)
    assert np.abs(np.asarray(down, np.float32).mean(1)).max() < 2e-2
    # what it took away is the same for every row of an output's column
    moved = np.asarray(params["layers_0"]["attn"]["o_proj"]) - \
        np.asarray(layer["attn"]["o_proj"])
    np.testing.assert_allclose(moved, np.broadcast_to(moved[:1], moved.shape),
                               atol=1e-6)


def test_the_two_router_bias_draws_say_how_they_draw():
    """Uniform within the range, normal at it: what the two functions
    both called ``_bias_init`` did."""
    class Cfg:
        router_bias_range = 0.1
    key = jax.random.PRNGKey(2)
    u = np.asarray(blocks.uniform_bias_init(Cfg)(key, (4096,), jnp.float32))
    n = np.asarray(blocks.normal_bias_init(Cfg)(key, (4096,), jnp.float32))
    assert np.abs(u).max() <= 0.1 and abs(u.std() - 0.1 / 3 ** 0.5) < 5e-3
    assert np.abs(n).max() > 0.2 and abs(n.std() - 0.1) < 5e-3


def test_plain_rotary_angles_leave_the_head_axis_to_the_caller():
    from deepspeed_tpu.models import lfm2_moe, ling_hybrid
    positions = jnp.asarray([[0, 1, 5], [7, 2, 3]])
    cos, sin = blocks.rope_cos_sin(positions, 8, 1e4)
    assert cos.shape == sin.shape == (2, 3, 4) and cos.dtype == jnp.float32
    inv = 1.0 / 1e4 ** (np.arange(0, 8, 2, dtype=np.float32) / 8)
    want = np.asarray(positions, np.float32)[..., None] * inv
    np.testing.assert_allclose(cos, np.cos(want), atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(want), atol=1e-6)
    # the two models' own, over their widths
    lf = lfm2_moe.lfm2_moe_tiny()
    c, s = lfm2_moe.rope_cos_sin(lf, positions)
    assert c.shape == (2, 3, 1, lf.head_dim // 2)
    np.testing.assert_array_equal(
        c[:, :, 0], blocks.rope_cos_sin(positions, lf.head_dim,
                                        lf.rope_theta)[0])
    lg = ling_hybrid.ling_hybrid_tiny()
    c, s = ling_hybrid.rope_cos_sin(lg, positions)
    assert c.shape == (2, 3, lg.qk_rope_head_dim // 2)
