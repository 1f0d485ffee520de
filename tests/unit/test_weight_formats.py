"""The serving engine places its weights in the format the decode
program's compiler asks for (ISSUE 54; `inference/engine.py`:
`asked_weight_formats`, `InferenceEngine._place_weights`), on the CPU
backend with `test_inference_engine.py`'s toy model. Weights on the
host's CPU are not asked about (`_askable`: the CPU's compiler keeps a
parameter in the default layout, so the answer is known), nor are those
of a backend whose arrays name no layout: the engine is the parent's,
bit for bit, and traces nothing as it is built. With the question let
through in the test the CPU's compiler does ask for the format the
weights lie in and nothing is placed; with its answer turned round the
whole placing path runs here (the CPU holds a column-major array as
well as the chip): the counters, the committed pool, the two-program
contract through a reset, the caller's tree left alone, the registry's
twin lowering the program that runs. The chip's own answer is
`test_tpu_compile_weight_formats.py`'s."""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from deepspeed_tpu.analysis.hlo import parameter_copies
from deepspeed_tpu.inference import engine as engine_module
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from deepspeed_tpu.telemetry import programs, spans
from tests.unit.test_inference_engine import identity_tables

PROMPT = [3, 1, 4, 1, 5]        # two prefill chunks of 4


def toy():
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=4, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def build(model, params):
    """An engine and the counters its ``setup/engine/params`` span got."""
    since = spans.clock()
    eng = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (16, 32), "prefill_chunk": 4})
    records = spans.recent(since)
    placing = [r[3] for r in records if r[0] == "setup/engine/params"]
    assert len(placing) == 1, [r[0] for r in records]
    asked = [r[0] for r in records if "/jax/" in r[0]
             and "_decode_fn" in str(r[3].get("fun"))]
    return eng, placing[0], asked


def serve(eng):
    """The logits of a prompt's prefill and of two decode steps."""
    tables = identity_tables(eng)
    out = [eng.prefill(0, PROMPT, tables[0])]
    for position in (len(PROMPT), len(PROMPT) + 1):
        out.append(np.asarray(eng.decode([7, 0], [position, 0], tables)[1]))
    return out


def parents_logits(model, params):
    """What the parent of ISSUE 54 served: the two programs jitted as
    they are and handed the tree as it came, nothing asked or placed."""
    eng, _, _ = build(model, params)
    eng.params = params
    eng._prefill = jax.jit(eng._prefill_fn, donate_argnums=(1,))
    eng._decode = jax.jit(eng._decode_fn, donate_argnums=(1,))
    return serve(eng)


def asked_here(monkeypatch):
    """The question let through for weights on the host's CPU."""
    monkeypatch.setattr(engine_module, "_askable", lambda leaf: True)


def turned_round(monkeypatch):
    """The question let through, and the compiler's answer with every
    matrix column-major, as the chip's compiler asks a projection to
    lie."""
    asked_here(monkeypatch)
    real = engine_module.asked_weight_formats

    def asked(fn, args, donate_argnums=()):
        return jax.tree_util.tree_map(
            lambda f: Format(Layout((1, 0), f.layout.tiling), f.sharding)
            if len(f.layout.major_to_minor) == 2 else f,
            real(fn, args, donate_argnums))
    monkeypatch.setattr(engine_module, "asked_weight_formats", asked)


def test_weights_on_the_hosts_cpu_are_not_asked_about():
    model, params = toy()
    eng, counters, asked = build(model, params)
    assert counters == {"weights_asked": 0, "weights_relaid": 0,
                        "weights_relaid_bytes": 0}
    assert asked == []      # nothing traced, lowered or compiled to ask
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(eng.params),
        jax.tree_util.tree_leaves(params)))
    for got, want in zip(serve(eng), parents_logits(model, params)):
        assert np.array_equal(got, want)


def test_which_weights_the_compiler_is_asked_about(monkeypatch):
    from jax._src import array
    x = jnp.ones((4, 4))
    askable = engine_module._askable
    assert not askable(np.ones((4, 4)))         # no device array
    assert not askable(x)                       # on the host's CPU
    chip = type("Device", (), {"platform": "tpu"})()
    monkeypatch.setattr(array.ArrayImpl, "devices", lambda self: {chip})
    assert askable(x)
    monkeypatch.setattr(
        array.ArrayImpl, "format",
        property(lambda self: Format(None, self.sharding)))
    assert not askable(x)                       # no layout named


def test_asked_the_cpu_wants_what_is_there_and_nothing_is_placed(
        monkeypatch):
    asked_here(monkeypatch)
    model, params = toy()
    eng, counters, asked = build(model, params)
    leaves = jax.tree_util.tree_leaves(params)
    assert counters == {"weights_asked": len(leaves), "weights_relaid": 0,
                        "weights_relaid_bytes": 0}
    # one question of the decode program, under the span that places
    assert asked == ["setup/engine/params/jax/" + leaf
                     for leaf in ("trace", "lower", "backend_compile")]
    # no copy, no second tree: the engine holds the arrays it was handed
    assert all(a is b for a, b in
               zip(jax.tree_util.tree_leaves(eng.params), leaves))
    assert not jax.tree_util.tree_leaves(eng.cache)[0].committed
    for got, want in zip(serve(eng), parents_logits(model, params)):
        assert np.array_equal(got, want)
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


def test_weights_that_are_no_device_arrays_are_not_asked():
    model, params = toy()
    eng, counters, asked = build(model, jax.tree_util.tree_map(
        np.asarray, params))
    assert counters["weights_asked"] == 0 and asked == []
    for got, want in zip(serve(eng), parents_logits(model, params)):
        assert np.array_equal(got, want)


def test_a_turned_answer_is_placed_leaf_by_leaf(monkeypatch):
    model, params = toy()
    want = parents_logits(model, params)
    turned_round(monkeypatch)
    logged = []
    handler = logging.Handler()
    handler.emit = lambda record: logged.append(record.getMessage())
    monkeypatch.setattr(engine_module.logger, "handlers", [handler])
    eng, counters, _ = build(model, params)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    matrices = [(jax.tree_util.keystr(p), x) for p, x in leaves
                if x.ndim == 2]
    assert counters == {
        "weights_asked": len(leaves), "weights_relaid": len(matrices),
        "weights_relaid_bytes": sum(x.nbytes for _, x in matrices)}
    # one line of the engine's log names them by path
    line = [text for text in logged
            if "placed in the format the decode program asks for" in text]
    assert len(line) == 1 and all(path in line[0] for path, _ in matrices)
    for (_, old), new in zip(leaves, jax.tree_util.tree_leaves(eng.params)):
        assert new.shape == old.shape and new.dtype == old.dtype
        if old.ndim == 2:
            assert new.committed
            assert new.format.layout.major_to_minor == (1, 0)
            assert np.array_equal(new, old)
        else:
            assert new is old       # lies right: the array it was
        # the tree is the caller's: nothing was donated
        assert not old.is_deleted()
    # the pool and the sampling key start committed, as the programs
    # hand them back: one compile a program, through a reset too
    assert all(x.committed for x in jax.tree_util.tree_leaves(eng.cache))
    for got, parent in zip(serve(eng), want):
        np.testing.assert_allclose(got, parent, rtol=1e-5, atol=1e-5)
    eng.reset()
    assert all(x.committed for x in jax.tree_util.tree_leaves(eng.cache))
    serve(eng)
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}
    assert eng.recompile_findings() == []
    # a second engine on the caller's tree is placed again, the same
    again, counters2, _ = build(model, params)
    assert counters2 == counters


@pytest.mark.full_compile
def test_the_registered_twin_lowers_the_program_that_runs(monkeypatch):
    model, params = toy()
    plain, _, _ = build(model, params)
    n = len(jax.tree_util.tree_leaves(params))
    decode_text = lambda eng: eng._decode.lower(            # noqa: E731
        *eng.decode_lowering_args()).compile().as_text()
    # as the CPU's compiler asked, no weight is copied on a call
    assert parameter_copies(decode_text(plain), n) == []
    turned_round(monkeypatch)
    eng, counters, _ = build(model, params)
    served = eng._decode.lower(*eng.decode_lowering_args()).compile()
    layouts = [f.layout.major_to_minor for f in
               jax.tree_util.tree_leaves(served.input_formats[0][0])]
    turned = [i for i, layout in enumerate(layouts) if layout == (1, 0)]
    assert len(turned) == counters["weights_relaid"] > 0
    # the program that runs takes the placed weights as they lie; here
    # that is the chip's fault mirrored (the CPU wanted them as they
    # were and re-lays the projections on every call), and the reader
    # sees it
    copied = [i for i, _ in parameter_copies(served.as_text(), n)]
    assert copied and set(copied) <= set(turned)
    # the twin is handed the same formats and lowers the same program
    twin = programs.compiled_text("decode")
    params_of = lambda text: sorted(                        # noqa: E731
        line.split(" parameter(")[0].split(" = ")[1]
        for line in text.splitlines() if " parameter(" in line)
    assert params_of(twin) == params_of(served.as_text())
    assert sum("{0,1}" in p for p in params_of(twin)) >= len(turned)
    assert [i for i, _ in parameter_copies(twin, n)] == copied


@pytest.mark.full_compile
def test_shapes_keeps_a_committed_arrays_format():
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    turned = jax.device_put(x, Format(Layout((1, 0), ()), x.sharding))
    committed = jax.device_put(x, x.sharding)
    tree = programs.shapes({"t": turned, "c": committed, "u": x, "n": 3})
    assert tree["n"] == 3
    assert tree["t"].format == turned.format
    assert tree["t"].format.layout.major_to_minor == (1, 0)
    assert tree["c"].format == committed.format
    assert tree["u"].sharding is None and tree["u"].format.layout is None
    for name in "tcu":
        assert (tree[name].shape, tree[name].dtype) == (x.shape, x.dtype)
    # a jit handed the shapes asks for the layouts the arrays lie in
    lowered = jax.jit(lambda t, c: t @ c.T).lower(tree["t"], tree["c"])
    assert [f.layout.major_to_minor for f in
            lowered.compile().input_formats[0]] == [(1, 0), (0, 1)]


HLO = """HloModule m

%fused (p: f32[4,8]) -> f32[4,8] {
  %p = f32[4,8]{1,0} parameter(0)
  ROOT %copy.9 = f32[4,8]{0,1} copy(%p)
}

ENTRY %main (a: bf16[8,4], b: bf16[8,4], c: f32[2]) -> bf16[4,8] {
  %a = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="a"}
  %b = bf16[8,4]{1,0} parameter(1)
  %c = f32[2]{0} parameter(2)
  %bitcast.1 = bf16[4,8]{0,1} bitcast(%a)
  %copy.1 = bf16[4,8]{1,0:T(8,128)(2,1)S(1)} copy(%bitcast.1), backend_config={}
  %copy.2 = bf16[8,4]{0,1} copy(bf16[8,4]{1,0} %b)
  %copy.3 = f32[2]{0} copy(%c)
  %add = bf16[8,4]{1,0} add(%b, %b)
  %copy.4 = bf16[8,4]{0,1} copy(%add)
  %cs = (bf16[8,4]{1,0}, bf16[8,4]{1,0}, u32[]) copy-start(%b)
  ROOT %dot = bf16[4,8]{1,0} fusion(%copy.1, %copy.2), kind=kOutput, calls=%fused
}
"""


@pytest.mark.parametrize("n_params, want", [
    (3, [(0, "bf16[4,8]{1,0:T(8,128)(2,1)S(1)}"), (1, "bf16[8,4]{0,1}"),
         (2, "f32[2]{0}")]),
    (2, [(0, "bf16[4,8]{1,0:T(8,128)(2,1)S(1)}"), (1, "bf16[8,4]{0,1}")]),
    (1, [(0, "bf16[4,8]{1,0:T(8,128)(2,1)S(1)}")]),
    (0, [])])
def test_parameter_copies_reads_the_entry_computations_own(n_params, want):
    """A parameter's copy directly or through a bitcast; not a computed
    value's, not a fusion's inner copy, not an asynchronous prefetch."""
    assert parameter_copies(HLO, n_params) == want
