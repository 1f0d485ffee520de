"""`test_tpu_compile.py` for the formats the serving engine places its
weights in (ISSUE 54): the decode programs of the three models whose
projections XLA re-laid on every call (Laguna's and MiMo's three-layer
shares of `test_tpu_compile_laguna.py` / `test_tpu_compile_mimo_v2.py`,
Kimi's two-layer share of `test_tpu_compile.py`, at the published
widths), compiled (not run) for a described ``v5e:2x2``. With the
weights in the default format each holds the re-layout ``copy`` of a
projection that the chip's trace showed (the premise, pinned); with the
weights in the formats `inference/engine.py:asked_weight_formats` reads
off the compiler, as `InferenceEngine` places them, no ``copy`` of a
weight is left in the decode program, nor in the prefill program handed
the same formats. A file of its own, as the models' are; the fixtures
and helpers are `test_tpu_compile.py`'s."""

import jax
import jax.numpy as jnp
import pytest

from tests.unit.test_tpu_compile import (       # noqa: F401 (fixtures)
    MLA_BUCKET, MLA_PAGES, MLA_ROWS, PAGE, _compiled_not_interpreted, chip,
    topo)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

CHUNK = 1024


def laguna():
    from deepspeed_tpu.models import laguna as lg
    from tests.unit.test_tpu_compile_laguna import BUCKET, PAGES, ROWS
    cfg = lg.laguna_s_2_1_share(
        n_layer=3, layer_types=("full_attention", "sliding_attention",
                                "full_attention"),
        num_attention_heads_per_layer=(48, 72, 48))
    model = lg.LagunaLM(cfg)
    return (model, lambda k: lg.init_laguna_params(model, k),
            cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES), ROWS)


def mimo():
    from deepspeed_tpu.models import mimo_v2 as mm
    from tests.unit.test_tpu_compile_mimo_v2 import BUCKET, PAGES, ROWS
    cfg = mm.mimo_v2_5_share(n_layer=3, hybrid_layer_pattern=(0, 1, 0))
    model = mm.MimoV2LM(cfg)
    return (model, lambda k: mm.init_mimo_v2_params(model, k),
            cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES), ROWS)


def kimi():
    from deepspeed_tpu.models import mla_moe as mm
    cfg = mm.kimi_k2_share(n_layer=2)
    model = mm.MlaMoeLM(cfg)
    return (model, lambda k: mm.init_mla_moe_params(model, k),
            cfg.cache_spec(MLA_ROWS, MLA_BUCKET, page_size=PAGE,
                           n_pages=MLA_PAGES), MLA_ROWS)


# family -> (its builder, the weight whose re-layout the chip's trace
# named, as the copy's result reads: the parameter's dimensions turned
# round, or as they are in the layout ``{0,1}``)
FAMILIES = {"laguna": (laguna, "bf16[9216,3072]"),      # a window q_proj
            "mimo": (mimo, "bf16[4096,12288]"),         # a q_proj
            "kimi": (kimi, "bf16[1536,12288]")}         # q_b_proj


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_weight_is_re_laid_in_the_formats_the_engine_asks_for(
        chip, monkeypatch, family):
    from deepspeed_tpu.analysis.hlo import parameter_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.inference.engine import asked_weight_formats

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.ops.pallas.latent_prefill",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    build, premise = FAMILIES[family]
    model, init, spec, rows = build()
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(init, jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(lambda: init_kv_cache(spec)))
    i32 = lambda *shape: chip(shape, jnp.int32)         # noqa: E731
    width = spec.table_width
    n_weights = len(jax.tree_util.tree_leaves(params))

    def decode(params, cache, tokens, positions, tables):
        live = (tables[:, 0] != 0).astype(jnp.int32)
        return model.serve_apply(
            params, cache, tokens[:, None], positions[:, None], tables,
            jnp.arange(rows, dtype=jnp.int32), live,
            attn_impl="flash", attn_block_k=PAGE)

    def prefill(params, cache, tokens, positions, table, slots, n_valid):
        return model.serve_apply(params, cache, tokens, positions, table,
                                 slots, n_valid, attn_impl="flash")

    decode_args = (cache, i32(rows), i32(rows), i32(rows, width))
    prefill_args = (cache, i32(1, CHUNK), i32(1, CHUNK), i32(1, width),
                    i32(1), i32(1))

    def copies(fn, params, args):
        text = jax.jit(fn, donate_argnums=1).lower(
            params, *args).compile().as_text()
        return parameter_copies(text, n_weights)

    # the premise: in the default format the projection is re-laid
    default = copies(decode, params, decode_args)
    assert premise in [result.split("{")[0] for _, result in default], \
        default

    asked = asked_weight_formats(decode, (params,) + decode_args, 1)
    turned = [f for f in jax.tree_util.tree_leaves(asked)
              if f.layout.major_to_minor == (1, 0)]
    assert len(turned) >= len(default), (len(turned), default)
    placed = jax.tree_util.tree_map(
        lambda a, f: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=f),
        params, asked)
    assert copies(decode, placed, decode_args) == []
    # the prefill program takes the formats decode asked for, and keeps
    # no re-layout either (its prefetch of a weight into fast memory is
    # a copy-start / copy-done pair in the layout the weight lies in)
    assert copies(prefill, placed, prefill_args) == []
