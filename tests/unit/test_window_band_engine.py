"""Who is told the engine's ``attention_impl`` for a prefill chunk, and
what the ``prefill`` span says of the window layers' bands (ISSUE 52).

`engine._prefill_fn` hands ``attn_impl`` (and the TP mesh, ``None``
here) to every model since ISSUE 58: a spec with page groups (the band's
kernel under ``"flash"``), a latent pool (the prefill kernel) and a
per-head pool (the chunk's kernel, where `cache.chunk_kernel_takes` the
call: never at these toy float32 shapes, so a GPT-2's and a hybrid
model's prefill programs are what they were). The span's
``attn_window_calls`` / ``attn_window_calls_kernel`` are there for a spec
with page groups and for no other, and the second counts what the call
site's rule sends to the kernel (`cache.band_kernel_takes`: a window
longer than the kernel's smallest query block, which the tests below cut
to 8 so that toy Laguna's window of 16 is long and toy MiMo's of 8 is
not, as 512 and 128 are against 128 on the chip); a per-head pool's span
carries ``attn_plain_calls`` / ``attn_plain_calls_kernel`` in their
place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.telemetry import spans

F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
KEY = jax.random.PRNGKey(0)
# a prompt of 41 tokens: two calls of 32, three of 16
PROMPT = list(range(1, 42))


def _gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny
    model = GPT2LMHead(gpt2_tiny(n_layer=1, dtype=jnp.float32))
    return model, model.init(KEY, jnp.zeros((1, 8), jnp.int32))["params"]


def _hybrid():
    from deepspeed_tpu.models import granite_hybrid as gh
    model = gh.GraniteHybridLM(gh.granite_hybrid_tiny(**F32))
    return model, gh.init_granite_hybrid_params(model, KEY)


def _mla():
    from deepspeed_tpu.models import mla_moe as mm
    model = mm.MlaMoeLM(mm.mla_moe_tiny(**F32))
    return model, mm.init_mla_moe_params(model, KEY)


def _laguna():
    from deepspeed_tpu.models import laguna as lg
    model = lg.LagunaLM(lg.laguna_tiny(**F32))
    return model, lg.init_laguna_params(model, KEY)


def _mimo():
    from deepspeed_tpu.models import mimo_v2 as mm
    model = mm.MimoV2LM(mm.mimo_v2_tiny(**F32))
    return model, mm.init_mimo_v2_params(model, KEY)


# name: builder, (prefill_chunk, page_size)
MODELS = {
    "gpt2": (_gpt2, (16, 8)),
    "hybrid": (_hybrid, (16, 8)),
    "latent_pool": (_mla, (16, 8)),
    "laguna": (_laguna, (32, 4)),
    "mimo_v2": (_mimo, (16, 8)),
}


# whose window is longer than the (cut) smallest query block
LONG = {"laguna": True, "mimo_v2": False}


@pytest.fixture(autouse=True)
def no_trace_outlives_its_query_block():
    """`_band_call` is jitted and its blocks are no static argument: a
    trace made under a cut `QUERY_BLOCK` goes with the test."""
    from deepspeed_tpu.ops.pallas import window_prefill as wp
    yield
    wp._band_call.clear_cache()


def build(name, impl, monkeypatch, query_block=8):
    """The toy model's engine, every call of its ``serve_apply`` noted."""
    from deepspeed_tpu.ops.pallas import window_prefill as wp
    monkeypatch.setattr(wp, "QUERY_BLOCK", query_block)
    wp._band_call.clear_cache()
    make, (chunk, page) = MODELS[name]
    model, params = make()
    told = []
    real = type(model).serve_apply

    def noted(self, *args, **kwargs):
        told.append(dict(kwargs))
        return real(self, *args, **kwargs)

    monkeypatch.setattr(type(model), "serve_apply", noted)
    eng = InferenceEngine(model, params, config=dict(
        max_batch=2, seq_buckets=(64,), prefill_chunk=chunk,
        page_size=page, attention_block_k=page, attention_impl=impl))
    return eng, told


def prefill_attrs(eng):
    t0 = spans.clock()
    table = np.arange(1, eng.table_width + 1)
    eng.prefill(0, PROMPT, table)
    return [r for r in spans.recent(t0) if r[0] == "prefill"][-1][3]


@pytest.mark.parametrize("impl", ["flash", "dense"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_who_is_told_the_attention_impl(monkeypatch, name, impl):
    eng, told = build(name, impl, monkeypatch)
    eng._prefill.lower(*eng.prefill_lowering_args())
    assert told == [{"attn_impl": impl, "attn_mesh": None}]


@pytest.mark.parametrize("impl", ["flash", "dense"])
@pytest.mark.parametrize("name", ["laguna", "mimo_v2"])
def test_prefill_span_counts_the_window_layers_calls(monkeypatch, name,
                                                     impl):
    """Chunk calls times the spec's window layers, through the band's
    kernel under ``"flash"`` where the window is long, and not under
    ``"dense"``."""
    eng, _ = build(name, impl, monkeypatch)
    layers = sum(len(g.layers) for g in eng.spec.groups if g.window)
    assert layers > 0
    attrs = prefill_attrs(eng)
    calls = attrs["chunks"] * layers
    assert attrs["chunks"] == -(-len(PROMPT) // eng.prefill_chunk)
    assert attrs["attn_window_calls"] == calls
    assert attrs["attn_window_calls_kernel"] == \
        (calls if impl == "flash" and LONG[name] else 0)
    assert attrs["attn_prefix_blocks_full"] > 0


@pytest.mark.parametrize("name", ["laguna", "mimo_v2"])
def test_toy_windows_are_short_against_the_real_query_block(monkeypatch,
                                                            name):
    eng, _ = build(name, "flash", monkeypatch, query_block=128)
    attrs = prefill_attrs(eng)
    assert attrs["attn_window_calls"] > 0
    assert attrs["attn_window_calls_kernel"] == 0


@pytest.mark.parametrize("name", ["gpt2", "hybrid", "latent_pool"])
def test_other_specs_spans_carry_no_window_calls(monkeypatch, name):
    eng, _ = build(name, "flash", monkeypatch)
    attrs = prefill_attrs(eng)
    assert "attn_window_calls" not in attrs
    assert "attn_window_calls_kernel" not in attrs
    assert ("attn_blocks" in attrs) == (name == "latent_pool")
    # a per-head pool's chunks a layer, none through the chunk's kernel
    # at a toy float32 chunk of 16 (ISSUE 58)
    assert ("attn_plain_calls" in attrs) == (name != "latent_pool")
    if name != "latent_pool":
        assert attrs["attn_plain_calls"] == \
            attrs["chunks"] * eng.spec.n_layer > 0
        assert attrs["attn_plain_calls_kernel"] == 0
    # nor a model without Mamba-2 mixers any scan calls (ISSUE 56)
    assert ("ssd_scan_calls" in attrs) == (name == "hybrid")


@pytest.mark.parametrize("name", ["laguna", "mimo_v2"])
def test_flash_prefill_runs_the_band_kernel_and_no_other_new_one(
        monkeypatch, name):
    """Under ``"flash"`` a window layer's chunk goes through
    `window_prefill_band` where the window is long, a full layer's
    through the XLA walk as before (``impl`` is ignored there); under
    ``"dense"`` nothing calls it."""
    from deepspeed_tpu.ops.pallas import window_prefill as wp

    calls = []
    real = wp.window_prefill_band

    def noted(q, *args, **kwargs):
        calls.append((q.shape, kwargs["window"]))
        return real(q, *args, **kwargs)

    import deepspeed_tpu.ops.pallas as pallas
    monkeypatch.setattr(pallas, "window_prefill_band", noted)
    for impl in ("dense", "flash"):
        eng, _ = build(name, impl, monkeypatch)
        del calls[:]
        eng._prefill.lower(*eng.prefill_lowering_args())
        window = [g for g in eng.spec.groups if g.window]
        layers = sum(len(g.layers) for g in window)
        assert len(calls) == (
            layers if impl == "flash" and LONG[name] else 0)
        assert {w for _, w in calls} <= {g.window for g in window}
