"""Each model's compiled programs carry the vocabulary's scopes
(`deepspeed_tpu/telemetry/scopes.py`), at toy size on the CPU: the
seven served models' ``prefill`` and ``decode`` and the GPT-2 and OLMoE
train steps, a case a program. The scopes cost nothing until somebody
asks: they are not in the lowered text, building an engine lowers
nothing, and what an engine registers holds no array and not the
engine."""

import gc
import importlib
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.telemetry import programs, scopes, spans

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

# what carries the device's time
OPCODES = {"fusion", "dot", "convolution", "custom-call", "copy", "sort",
           "gather", "scatter", "dynamic-update-slice"}
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([a-z\-]+)\(")

# family -> (module, tiny config, model, init, (chunk, page, seq, rows))
SERVED = {
    "granite": ("granite_hybrid", "granite_hybrid_tiny", "GraniteHybridLM",
                "init_granite_hybrid_params", (16, 8, 64, 3)),
    "kimi": ("mla_moe", "mla_moe_tiny", "MlaMoeLM", "init_mla_moe_params",
             (16, 8, 64, 4)),
    "nemotron": ("nemotron_h", "nemotron_h_tiny", "NemotronHLM",
                 "init_nemotron_h_params", (16, 8, 64, 3)),
    "qwen3_next": ("qwen3_next", "qwen3_next_tiny", "Qwen3NextLM",
                   "init_qwen3_next_params", (16, 8, 64, 3)),
    "mimo": ("mimo_v2", "mimo_v2_tiny", "MimoV2LM", "init_mimo_v2_params",
             (16, 8, 64, 3)),
    "laguna": ("laguna", "laguna_tiny", "LagunaLM", "init_laguna_params",
               (32, 4, 128, 3)),
}
# scopes each program must hold besides the ones every program has
EXPECT = {
    "gpt2": {"ds_mlp", "ds_kv_write"},
    "granite": {"ds_ssm_mixer", "ds_ssm_scan", "ds_mlp"},
    "kimi": {"ds_mla_project", "ds_experts", "ds_mlp", "ds_moe_experts"},
    "nemotron": {"ds_ssm_mixer", "ds_experts", "ds_moe_latent_up"},
    "qwen3_next": {"ds_gdn_mixer", "ds_experts", "ds_attn_gate"},
    "mimo": {"ds_experts", "ds_mlp"},
    "laguna": {"ds_experts", "ds_mlp", "ds_attn_gate"},
}
EVERY_SERVED = {"ds_embed", "ds_attn_qkv", "ds_attn_out", "ds_head"}
PLAIN = {"gpt2", "granite", "nemotron", "qwen3_next"}


def served_engine(family):
    if family == "gpt2":
        from deepspeed_tpu.models import gpt2
        model = gpt2.GPT2LMHead(gpt2.gpt2_tiny())
        params = gpt2.init_gpt2_params(model, jax.random.PRNGKey(0))
        chunk, page, seq, rows = 16, 16, 64, 4
    else:
        mod, tiny, cls, init, (chunk, page, seq, rows) = SERVED[family]
        m = importlib.import_module("deepspeed_tpu.models." + mod)
        model = getattr(m, cls)(getattr(m, tiny)(
            dtype=jnp.float32, param_dtype=jnp.float32))
        params = getattr(m, init)(model, jax.random.PRNGKey(0))
    return InferenceEngine(model, params, config={
        "max_batch": rows, "seq_buckets": (seq,), "prefill_chunk": chunk,
        "page_size": page, "attention_block_k": page,
        "attention_impl": "dense"})


def train_engine(family):
    if family == "gpt2":
        from deepspeed_tpu.models import gpt2
        model = gpt2.GPT2LMHead(gpt2.gpt2_tiny())
        params = gpt2.init_gpt2_params(model, jax.random.PRNGKey(0))
        loss_fn = gpt2.make_gpt2_loss_fn(model)
    else:
        from deepspeed_tpu.models import olmoe
        model = olmoe.OlmoeLM(olmoe.olmoe_tiny(dtype=jnp.bfloat16))
        params = olmoe.init_olmoe_params(model, jax.random.PRNGKey(0))
        loss_fn = olmoe.make_olmoe_loss_fn(model)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8, "bf16": {"enabled": True},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0},
        loss_fn=loss_fn, params=params)
    return engine


def coverage(text):
    """``(share of the instructions of `OPCODES` that name an origin and
    lie under a vocabulary scope, the scopes seen, those under none)``.
    An instruction without an ``op_name`` is the compiler's own (the
    CPU's float32 copies of bfloat16 weights, a loop's copies): it has
    no origin to name and is not the program's to scope."""
    under, bare, seen = 0, [], set()
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if not m or m.group(2) not in OPCODES:
            continue
        origin = re.search(r'op_name="([^"]*)"', line)
        if origin is None:
            continue
        seen.update(scopes.chain(origin.group(1)))
        if scopes.innermost(origin.group(1)):
            under += 1
        else:
            bare.append(origin.group(1))
    return under / max(under + len(bare), 1), seen, bare


@pytest.fixture(scope="module", params=["gpt2", *SERVED])
def served(request):
    return request.param, served_engine(request.param)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_served_program_is_under_scopes(served, program):
    family, engine = served
    assert {"prefill", "decode"} <= set(programs.registered())
    share, seen, bare = coverage(programs.compiled_text(program))
    assert share >= 0.95, (family, program, share, bare[:10])
    want = EVERY_SERVED | EXPECT[family]
    if family in PLAIN:     # cached_attention outside groups and latents
        want = want | {f"ds_attn_{program}_plain"}
    assert want <= seen, (family, program, sorted(want - seen))
    # every instruction, no marker; an op_name or "" each
    known = programs.op_names(program)
    assert len(known) > 50 and all(isinstance(v, str)
                                   for v in known.values())
    # compile-time metadata only: the lowered text prints no location
    fn, args = (engine._prefill, engine.prefill_lowering_args()) \
        if program == "prefill" else \
        (engine._decode, engine.decode_lowering_args())
    lowered = fn.lower(*args).as_text()
    assert not [s for s in scopes.SCOPES if s in lowered]


@pytest.mark.parametrize("family", ["gpt2", "olmoe"])
def test_a_train_step_is_under_scopes(family):
    engine = train_engine(family)
    since = spans.clock()
    engine.train_batch({"input_ids": np.zeros((8, 32), np.int32)})
    assert "train_step" in programs.registered()
    # the step's first call lowered it once; registering lowered nothing
    lowers = [r for r in spans.recent(since) if r[0].endswith("/jax/lower")
              and "train_step" in str(r[3].get("fun"))]
    assert len(lowers) == 1, lowers
    share, seen, bare = coverage(programs.compiled_text("train_step"))
    assert share >= 0.95, (family, share, bare[:10])
    want = {"ds_embed", "ds_attn_qkv", "ds_attn_train", "ds_attn_out",
            "ds_head", "ds_loss", "ds_grad_epilogue", "ds_opt_update"}
    want |= {"ds_mlp"} if family == "gpt2" else \
        {"ds_experts", "ds_moe_experts", "ds_param_cast"}
    assert want <= seen, (family, sorted(want - seen))
    from deepspeed_tpu.analysis.audit import _engine_fn_args
    fn, args = _engine_fn_args(
        engine, engine._shard_batch({"input_ids": np.zeros((8, 32),
                                                           np.int32)}),
        jax.random.PRNGKey(0), jnp.asarray(1e-3, jnp.float32))
    lowered = fn.lower(*args).as_text()
    assert not [s for s in scopes.SCOPES if s in lowered]


def test_building_an_engine_lowers_nothing_and_holds_no_array():
    gc.collect()
    since = spans.clock()
    engine = served_engine("gpt2")
    assert {"prefill", "decode"} <= set(programs.registered())
    ledger = [r for r in spans.recent(since) if "/jax/" in r[0]
              and ("_prefill_fn" in str(r[3].get("fun"))
                   or "_decode_fn" in str(r[3].get("fun")))]
    assert not ledger, ledger       # neither traced, lowered nor compiled
    # what is registered keeps neither the engine nor its arrays alive,
    # and outlives it: a benchmark's readers ask once the driver that
    # built the engine has returned
    gone, leaf = weakref.ref(engine), weakref.ref(
        jax.tree_util.tree_leaves(engine.params)[0])
    del engine
    gc.collect()
    assert gone() is None and leaf() is None
    known = programs.op_names("decode")
    assert known and any("ds_head" in v for v in known.values())
    assert programs.op_names("no_such_program") is None
    assert programs.compiled_text("no_such_program") is None


def test_a_dense_train_step_outlives_its_engine_and_holds_no_array():
    engine = train_engine("gpt2")
    engine.train_batch({"input_ids": np.zeros((8, 32), np.int32)})
    gone, leaf = weakref.ref(engine), weakref.ref(
        jax.tree_util.tree_leaves(engine.params)[0])
    del engine
    gc.collect()
    assert gone() is None and leaf() is None
    known = programs.op_names("train_step")
    assert known and any("ds_opt_update" in v for v in known.values())


def test_a_cache_another_tree_wrote_does_not_lend_its_names(tmp_path):
    """The persistent cache's key leaves locations out: a program that
    differs from a cached one by its scopes alone is served that one's
    executable, under its names. `programs.compiled_text` sees the
    scope missing and compiles under a key that holds the locations."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from deepspeed_tpu.telemetry import compile_cache
    knobs = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in knobs}
    for k, v in knobs.items():
        jax.config.update(k, v)
    cc.reset_cache()
    compile_cache.install()
    try:
        def program(x):
            return jnp.sin(x) @ x

        def scoped(x):
            with jax.named_scope("ds_mlp"):
                return jnp.sin(x) @ x
        scoped.__name__ = scoped.__qualname__ = "program"
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        jax.jit(program).lower(x).compile()     # the other tree's
        programs.register("borrowed", lambda: (scoped, (), (x,)))
        asked = []
        for _ in range(2):      # one call site: locations hold the stack
            c0 = compile_cache.counts()
            known = programs.op_names("borrowed")
            c1 = compile_cache.counts()
            assert any("ds_mlp" in v for v in known.values()), known
            asked.append((c1["hits"] - c0["hits"],
                          c1["misses"] - c0["misses"]))
        # the key without locations hits and the one with them misses;
        # from then on the second key answers too: no compile
        assert asked == [(1, 1), (2, 0)]
        assert getattr(
            jax.config,
            "jax_compilation_cache_include_metadata_in_key") is False
    finally:
        programs._programs.pop("borrowed", None)
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_what_the_compiler_added_is_laid_to_what_it_feeds(monkeypatch):
    """An instruction with no origin takes, marked, the ``op_name`` of
    the first instruction downstream that has one: a weight's prefetched
    slice belongs to the matmul that reads it."""
    text = """
%async_computation.3 (param_0.5: f32[8,8]) -> f32[2,8] {
  %param_0.5 = f32[8,8]{1,0} parameter(0)
  ROOT %slice.7 = f32[2,8]{1,0} slice(%param_0.5), slice={[0:2], [0:8]}
}

ENTRY %main (w: f32[8,8], x: f32[2,8]) -> (f32[2,8]) {
  %w = f32[8,8]{1,0} parameter(0)
  %x = f32[2,8]{1,0} parameter(1), metadata={op_name="x"}
  %slice-start.3 = ((f32[8,8]), f32[2,8], s32[]) async-start(%w), calls=%async_computation.3
  %slice-done.3 = f32[2,8]{1,0} async-done(%slice-start.3)
  %bitcast.1 = f32[2,8]{1,0} bitcast(%slice-done.3)
  %copy.4 = f32[2,8]{0,1} copy(%x), metadata={op_name="params['x']"}
  %add.5 = f32[2,8]{0,1} add(%copy.4, %copy.4), metadata={op_name="jit(d)/LM/add"}
  %fusion.2 = f32[2,8]{1,0} fusion(%add.5, %bitcast.1), kind=kOutput, calls=%f, metadata={op_name="jit(d)/LM/ds_mlp/dot_general" stack_frame_id=4}
  %copy.9 = f32[2,8]{0,1} copy(%fusion.2)
  ROOT %tuple.1 = (f32[2,8]) tuple(%copy.9)
}
"""
    monkeypatch.setattr(programs, "compiled_text", lambda name: text)
    known = programs.op_names("anything")
    fed = programs.FEEDS + "jit(d)/LM/ds_mlp/dot_general"
    assert known["fusion.2"] == "jit(d)/LM/ds_mlp/dot_general"
    assert known["slice-start.3"] == known["slice-done.3"] == fed
    assert known["bitcast.1"] == known["w"] == fed
    # a relayout copy that names the weight it copies is the matmul's
    # too; what computes keeps its own name, scoped or not
    assert known["copy.4"] == fed
    assert known["add.5"] == "jit(d)/LM/add" and known["x"] == "x"
    assert known["copy.9"] == known["tuple.1"] == ""   # feeds the result
    assert scopes.innermost(known["slice-done.3"]) == "ds_mlp"
    assert known["slice.7"] == ""       # its computation has no user here
