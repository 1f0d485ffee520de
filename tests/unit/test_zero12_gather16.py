"""ZeRO stages 1 and 2 under 16-bit compute: the float32 masters lie
sharded over ``data`` and the step gathers their 16-bit copy.

The reference gathers the updated fp16 shards and never the fp32 masters
(`zero/stage1.py:692`). Here the masters take the moments' layout
(`zero/sharding.py:build_zero_shardings`, ``sharded_masters``) and every
program that reads them under GSPMD — the dense train step,
``eval_batch``, ``backward`` — begins with one cast-then-gather of the
whole tree (`make_param_caster`: one ``shard_map`` under one
``custom_vjp``). Exactness: cast is elementwise, so cast∘gather ==
gather∘cast bit for bit; the cotangent is cast to fp32 before it is
resharded.

As in ``test_zero3_gather16.py`` the wire dtype is read off the SPMD
partitioner's pass dump, which is backend-independent: the final CPU
HLO re-widens a bf16 gather to f32.
"""

import glob
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.analysis.hlo import collective_ops
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.zero.sharding import (
    build_zero_shardings, make_param_caster)
from tests.unit.zero_fixtures import (
    HIDDEN, NLAYERS, PARAM_BYTES, build_engine, lowered_train_step,
    make_batch)

FP16 = {"fp16": {"enabled": True, "initial_scale_power": 20}}


def curve(engine, batch, steps=5):
    return [float(engine.train_batch(batch)) for _ in range(steps)]


@pytest.fixture(scope="module")
def stage0():
    """Stage 0's losses, a curve a (accum, precision)."""
    curves = {}

    def of(accum=1, fp16=False):
        if (accum, fp16) not in curves:
            engine = build_engine(0, accum=accum,
                                  precision=FP16 if fp16 else None)
            curves[accum, fp16] = (curve(engine, make_batch(accum)), engine)
        return curves[accum, fp16]
    return of


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("stage", [1, 2])
def test_losses_equal_stage0_bit_for_bit(stage0, stage, accum):
    engine = build_engine(stage, accum=accum)
    assert engine._sharded_masters()
    assert curve(engine, make_batch(accum)) == stage0(accum)[0]
    # one program: the sharded outputs come back as the inputs lay
    from deepspeed_tpu.analysis import compiled_cache_size
    assert compiled_cache_size(engine) == 1


@pytest.mark.parametrize("stage", [1, 2])
def test_fp16_overflow_skips_on_shards_as_stage0_does(stage0, stage):
    # a scale of 2^20 overflows after the first update: the skip's
    # `select` runs on shards, and must keep what stage 0 keeps
    want, e0 = stage0(fp16=True)
    engine = build_engine(stage, precision=FP16)
    assert curve(engine, make_batch()) == want
    assert engine.skipped_steps == e0.skipped_steps > 0
    assert float(engine.loss_scale) == float(e0.loss_scale)


@pytest.mark.parametrize("stage", [1, 2])
def test_eval_and_the_compat_loop_read_the_sharded_masters(stage0, stage):
    batch = make_batch()
    e0, engine = build_engine(0), build_engine(stage)
    assert float(engine.eval_batch(batch)) == float(e0.eval_batch(batch))
    for e in (e0, engine):          # forward / backward / step
        e.forward(batch)
        e.backward()
        e.step()
    leaf = engine.params["linear_0"]["kernel"]
    assert not leaf.sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(leaf),
                               np.asarray(e0.params["linear_0"]["kernel"]),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.full_compile
@pytest.mark.parametrize("stage", [1, 2])
def test_param_gathers_are_bf16_at_partitioner_level(tmp_path, stage):
    lowered_train_step(stage, compiler_options={
        "xla_dump_to": str(tmp_path), "xla_dump_hlo_pass_re": "spmd"})
    dumps = sorted(glob.glob(str(tmp_path / "*spmd-partition*")))
    assert dumps, "no spmd-partitioner dump produced"
    txt = open(dumps[-1]).read()
    shape = re.compile(r"=\s+(\w+)\[([\d,]*)\]")
    kernels, f32_sized = [], []
    for ln in txt.splitlines():
        if "all-gather(" not in ln:
            continue
        m = shape.search(ln)
        dims = [int(d) for d in m.group(2).split(",") if d]
        if dims == [HIDDEN, HIDDEN]:
            kernels.append(m.group(1))
        if m.group(1) == "f32" and int(np.prod(dims)) >= HIDDEN:
            f32_sized.append(ln.strip()[:120])
    # every kernel gathered, once, as bf16; and no float32 all-gather
    # the size of a parameter (a bias has HIDDEN elements) is left: the
    # update's refresh gather is gone from the program
    assert kernels == ["bf16"] * NLAYERS, kernels
    assert not f32_sized, f32_sized


def test_accumulation_gathers_once_a_step():
    # the caster is applied in front of the scan: the gathers of accum=4
    # sit outside the loop (multiplier 1) and total accum=1's bytes
    def gathered(accum):
        ops = [op for op in collective_ops(
            lowered_train_step(2, accum=accum).as_text())
            if op["op"] == "all-gather"]
        assert all(op["multiplier"] == 1 for op in ops), ops
        return sum(sum(op["dtype_bytes"].values()) for op in ops)
    assert gathered(4) == gathered(1) > 0


def _tree(n_leaves, mesh):
    params = {f"w{i}": jnp.full((16, 8), i + 0.3, jnp.float32)
              for i in range(n_leaves)}
    params["odd"] = jnp.ones((3, 5), jnp.float32)   # nothing 4 divides
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    sh = build_zero_shardings(params, specs, mesh, 2, sharded_masters=True)
    return jax.device_put(params, sh["param"]), sh["param"]


@pytest.mark.parametrize("n_leaves", [3, 96])
def test_caster_is_one_manual_region_whatever_the_tree(n_leaves):
    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])
    params, shardings = _tree(n_leaves, mesh)
    cast = make_param_caster(params, shardings, mesh, jnp.bfloat16)

    def loss(p):
        return sum(jnp.sum(x.astype(jnp.float32) ** 2)
                   for x in jax.tree_util.tree_leaves(cast(p)))

    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(params))
    assert text.count("shard_map") == 1, text.count("shard_map")
    assert text.count("custom_vjp_call") <= 1
    # the static split the `compile` event carries
    assert cast.plan == {
        "gather_leaves": n_leaves, "gather_bytes": n_leaves * 16 * 8 * 2,
        "replicated_leaves": 1, "replicated_bytes": 3 * 5 * 2}
    # and it is the plain cast, value and gradient
    want, g_want = jax.value_and_grad(lambda p: sum(
        jnp.sum(x.astype(jnp.bfloat16).astype(jnp.float32) ** 2)
        for x in jax.tree_util.tree_leaves(p)))(params)
    got, g_got = jax.jit(jax.value_and_grad(loss))(params)
    assert float(got) == float(want)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        assert a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_caster_keeps_a_tensor_parallel_axis_and_skips_tuple_specs():
    mesh = build_mesh({"data": 4, "model": 2})
    params = {"col": jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8),
              "both": jnp.ones((8, 8), jnp.float32)}
    specs = {"col": P(None, "model"), "both": P(("data", "model"))}
    sh = build_zero_shardings(params, specs, mesh, 1, sharded_masters=True)
    assert sh["param"]["col"].spec == P("data", "model")
    assert sh["param"]["both"].spec == P(("data", "model"))
    placed = jax.device_put(params, sh["param"])
    cast = make_param_caster(placed, sh["param"], mesh, jnp.bfloat16)
    out = jax.jit(cast)(placed)
    assert out["col"].dtype == jnp.bfloat16
    assert out["col"].sharding.spec == P(None, "model")
    np.testing.assert_array_equal(
        np.asarray(out["col"]), np.asarray(params["col"].astype(jnp.bfloat16)))
    np.testing.assert_array_equal(
        np.asarray(out["both"]),
        np.asarray(params["both"].astype(jnp.bfloat16)))


def test_no_caster_where_nothing_is_sharded():
    one = build_mesh({"data": 1}, devices=jax.devices()[:1])
    params, shardings = _tree(2, one)
    assert make_param_caster(params, shardings, one, jnp.bfloat16) is None
    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])
    params = {"odd": jnp.ones((3, 5), jnp.float32)}
    sh = build_zero_shardings(params, {"odd": P()}, mesh, 2,
                              sharded_masters=True)
    assert make_param_caster(params, sh["param"], mesh,
                             jnp.bfloat16) is None


def test_compile_event_says_whether_the_tree_took_the_gather(tmp_path):
    log = tmp_path / "t.jsonl"
    engine = build_engine(2, telemetry={"enabled": True,
                                        "jsonl_path": str(log)})
    engine.train_batch(make_batch())
    engine.telemetry.close()
    compiles = [json.loads(ln) for ln in open(log)
                if json.loads(ln).get("event") == "compile"]
    assert len(compiles) == 1
    assert compiles[0]["param_gather"] == {
        "gather_leaves": 2 * NLAYERS, "gather_bytes": PARAM_BYTES // 2,
        "replicated_leaves": 0, "replicated_bytes": 0}


@pytest.mark.parametrize("kind", ["pipeline", "quantized", "sparse",
                                  "onebit", "offload"])
def test_step_kinds_with_a_manual_region_keep_the_replicated_layout(kind):
    # the rule is decided from the step kind the engine already knows:
    # only the dense GSPMD step takes the caster
    class Of(DeepSpeedEngine):
        def __init__(self):
            self.compute_dtype = jnp.bfloat16
            self.dp_world_size = 8

        def zero_optimization_stage(self):
            return 2

        def _step_kind(self):
            return self.kind
    engine = Of()
    engine.kind = kind
    assert not engine._sharded_masters()
    engine.kind = "dense"
    assert engine._sharded_masters()


def test_quantized_comm_step_keeps_replicated_masters():
    engine = build_engine(2, comm_quantization={"enabled": True})
    assert engine._step_kind() == "quantized"
    assert engine.params["linear_0"]["kernel"].sharding.is_fully_replicated
    assert np.isfinite(curve(engine, make_batch(), steps=2)).all()


@pytest.mark.parametrize("direction", ["old_to_new", "new_to_old"])
def test_checkpoint_crosses_the_layouts(tmp_path, monkeypatch, direction):
    """A stage-2 checkpoint written under the replicated-masters layout
    (the parent's) loads under the sharded one and the reverse, and
    resumes to the same loss: a global ``jax.Array`` is the same array
    under either sharding."""
    batch = make_batch()

    def engine_with(layout):
        with monkeypatch.context() as m:
            if layout == "old":     # the parent's rule: never at 1 and 2
                m.setattr(DeepSpeedEngine, "_sharded_masters",
                          lambda self: False)
            engine = build_engine(2)
        replicated = engine.params["linear_0"]["kernel"] \
            .sharding.is_fully_replicated
        assert replicated == (layout == "old")
        return engine

    first, second = ("old", "new") if direction == "old_to_new" \
        else ("new", "old")
    writer = engine_with(first)
    curve(writer, batch, steps=2)
    writer.save_checkpoint(str(tmp_path), tag="t")
    writer._ckpt_manager.wait()
    want = curve(writer, batch, steps=2)

    with monkeypatch.context() as m:
        if second == "old":
            m.setattr(DeepSpeedEngine, "_sharded_masters",
                      lambda self: False)
        reader = build_engine(2)
        path, _ = reader.load_checkpoint(str(tmp_path), tag="t")
        assert path is not None
        leaf = reader.params["linear_0"]["kernel"]
        assert leaf.sharding.is_fully_replicated == (second == "old")
        assert curve(reader, batch, steps=2) == want


# sha256 of the one-device train step's lowered text on the parent
# (f205bc5), by `lower().as_text()` as below: the benchmark's one-chip
# training cells run stage 0 on one device and must not see this change;
# a one-device `data` axis at stage 2 takes the base spec and no caster.
# A PR that changes the dense step on purpose regenerates these (print
# the digest this test computes) and says so.
ONE_DEVICE_TEXT = {
    ("gpt2", 0): "6a053a0ff76bd9c59cd34cce949221fe1a59a880cd253d4acd65a69a36d6005a",
    ("olmoe", 0): "6d224752be02c000f7891327a9f3544b43144002b255a8d33341d7b64f82bf2c",
    ("gpt2", 2): "d28f227ccdd776a14aa5384ba57f479c7cc79bc894f353db5ac4b542b66c755d",
    ("olmoe", 2): "8327e587629e7762fae77aa4c19e5aaa7137cac8004ed8ded57c033b3bc8b090",
}


@pytest.mark.parametrize("model,stage", sorted(ONE_DEVICE_TEXT))
def test_one_device_step_lowers_to_the_parents_text(model, stage):
    if model == "gpt2":
        from deepspeed_tpu.models.gpt2 import (
            GPT2LMHead, gpt2_tiny, make_gpt2_loss_fn)
        net = GPT2LMHead(gpt2_tiny(dtype=jnp.bfloat16,
                                   use_flash_attention=True))
        params = net.init({"params": jax.random.PRNGKey(0)},
                          jnp.zeros((1, 8), jnp.int32))["params"]
        loss_fn, rows, vocab = make_gpt2_loss_fn(net), 8, 256
    else:
        from deepspeed_tpu.models.olmoe import (
            OlmoeLM, init_olmoe_params, make_olmoe_loss_fn, olmoe_tiny)
        net = OlmoeLM(olmoe_tiny(dtype=jnp.bfloat16))
        params = init_olmoe_params(net, jax.random.PRNGKey(0))
        loss_fn, rows, vocab = make_olmoe_loss_fn(net), 2, 200
    config = {"train_batch_size": rows, "bf16": {"enabled": True},
              "zero_optimization": {"stage": stage},
              "optimizer": {"type": "Adam", "params": {"lr": 3e-4}},
              "gradient_clipping": 1.0, "steps_per_print": 10 ** 9}
    mesh = build_mesh({"data": 1}, devices=jax.devices()[:1])
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=config, loss_fn=loss_fn, params=params, mesh=mesh)
    assert not engine._sharded_masters()
    assert engine._param_caster() is None
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, vocab, size=(rows, 64)).astype(np.int32)}
    engine.train_batch(batch)
    text = engine._compiled_train_step.lower(
        engine.params, engine.opt_state, engine.device_state,
        engine._shard_batch(batch), jax.random.PRNGKey(1),
        jnp.asarray(1e-3, jnp.float32)).as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == ONE_DEVICE_TEXT[model, stage], digest
