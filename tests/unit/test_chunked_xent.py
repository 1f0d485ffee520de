"""Chunked cross-entropy: parity with the dense head + the compiled-memory
win it exists for (the [B, T, V] logits are GPT-2's largest activation)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import (
    GPT2Config, GPT2LMHead, chunked_cross_entropy_sum_and_count,
    cross_entropy_sum_and_count, init_gpt2_params, make_gpt2_loss_fn)


def test_chunked_matches_dense_sum_and_count():
    rng = np.random.default_rng(0)
    B, T, M, V = 2, 12, 8, 32
    x = jnp.asarray(rng.standard_normal((B, T, M)), jnp.float32)
    wte = jnp.asarray(rng.standard_normal((V, M)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    labels = labels.at[0, 3].set(-100)    # ignore_index in the middle

    dense = cross_entropy_sum_and_count(x @ wte.T, labels)
    for chunk in (4, 5, 12, 64):          # incl. non-dividing + oversized
        ch = chunked_cross_entropy_sum_and_count(x, wte, labels, chunk)
        np.testing.assert_allclose(float(ch[0]), float(dense[0]), rtol=1e-6)
        assert int(ch[1]) == int(dense[1])


@pytest.mark.slow
def test_chunked_loss_fn_grads_match_dense():
    cfg_d = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=2,
                       n_head=2, dtype=jnp.float32)
    cfg_c = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=2,
                       n_head=2, dtype=jnp.float32, loss_chunk=8)
    model_d, model_c = GPT2LMHead(cfg_d), GPT2LMHead(cfg_c)
    params = init_gpt2_params(model_d, jax.random.PRNGKey(0), seq_len=32)
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, 64, (2, 32)).astype(np.int32)}

    ld, gd = jax.value_and_grad(
        lambda p: make_gpt2_loss_fn(model_d)(p, batch, None))(params)
    lc, gc = jax.value_and_grad(
        lambda p: make_gpt2_loss_fn(model_c)(p, batch, None))(params)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-6)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gd)[0],
            jax.tree_util.tree_flatten_with_path(gc)[0]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=1e-7,
                                   err_msg=str(pa))


@pytest.mark.full_compile
@pytest.mark.slow
def test_chunked_loss_cuts_compiled_logit_memory():
    """Compiled temp bytes of grad(loss) must drop by roughly the logits'
    footprint when chunking is on (the point of the feature)."""
    V, T, B = 2048, 256, 4
    mk = lambda chunk: GPT2LMHead(GPT2Config(
        vocab_size=V, n_positions=T, n_embd=64, n_layer=1, n_head=2,
        dtype=jnp.float32, loss_chunk=chunk))
    model_d, model_c = mk(0), mk(32)
    params = init_gpt2_params(model_d, jax.random.PRNGKey(0), seq_len=T)
    batch = {"input_ids": np.zeros((B, T), np.int32)}

    def temp_bytes(model):
        f = jax.jit(jax.grad(
            lambda p: make_gpt2_loss_fn(model)(p, batch, None)))
        mem = f.lower(params).compile().memory_analysis()
        return mem.temp_size_in_bytes

    dense_b, chunk_b = temp_bytes(model_d), temp_bytes(model_c)
    # Dense holds [B, T, V] fp32 logits (+ log_softmax residents) ≈ 8 MB
    # at these shapes; chunked peaks at [B, 32, V].
    assert chunk_b < dense_b * 0.6, (dense_b, chunk_b)


@pytest.mark.slow
def test_bert_chunked_mlm_loss_matches_dense():
    """BERT MLM: loss_chunk>0 computes the identical loss+grads without the
    [B, T, 30522] logits (decoder kernel AND bias flow through)."""
    from deepspeed_tpu.models.bert import (
        BertConfig, BertForMaskedLM, init_bert_params,
        make_bert_mlm_loss_fn)

    mk = lambda chunk: BertForMaskedLM(BertConfig(
        vocab_size=96, hidden_size=16, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=32,
        max_position_embeddings=32, loss_chunk=chunk))
    model_d, model_c = mk(0), mk(8)
    params = init_bert_params(model_d, jax.random.PRNGKey(0), seq_len=24)
    rng = np.random.default_rng(2)
    labels = np.full((2, 24), -100, np.int64)
    labels[:, ::5] = rng.integers(0, 96, labels[:, ::5].shape)
    batch = {"input_ids": rng.integers(0, 96, (2, 24)).astype(np.int32),
             "labels": labels}

    ld, gd = jax.value_and_grad(
        lambda p: make_bert_mlm_loss_fn(model_d)(p, batch, None))(params)
    lc, gc = jax.value_and_grad(
        lambda p: make_bert_mlm_loss_fn(model_c)(p, batch, None))(params)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-6)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gd)[0],
            jax.tree_util.tree_flatten_with_path(gc)[0]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=1e-7, err_msg=str(pa))


@pytest.mark.slow
def test_chunked_xent_with_zero3_matches_dense_curve():
    """loss_chunk composes with ZeRO-3 param sharding (the chunked path
    reads params['wte'] directly — GSPMD must handle the sharded table
    inside the scan body identically to the dense head).

    Tolerance history: round 3 observed ~1.5e-4 curve divergence — bf16
    rounding of per-chunk ``wte`` cotangent partials in the scan
    accumulation (the dense head gets one fp32-accumulated matmul).
    Round 5 removed that accumulation noise: the head primal stays fp32
    across the scan and the per-chunk cotangent is produced directly in
    fp32 (``_head_matmul``'s ``preferred_element_type`` backward), so
    cross-chunk sums never round to bf16. Measured divergence is now
    ~3.9e-5 after 5 Adam steps. The residue is irreducible for ANY
    chunked algorithm: chunked and dense produce fp32 cotangent sums that
    differ by summation order (~1e-7 rel), and the single downcast to the
    bf16 param dtype turns a boundary-straddling 1e-7 difference into a
    1-ulp (≈4e-3) flip on isolated elements, which Adam then amplifies
    into small curve drift. So: ZeRO-3 must be loss-transparent (sharded
    == unsharded curve, tight), and chunked-vs-dense must sit at 2e-4
    (~5x the observed 3.9e-5, 10x tighter than the pre-fix 2e-3 bound)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2LMHead,
                                           init_gpt2_params,
                                           make_gpt2_loss_fn)

    def train(chunk, zero_stage):
        cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=16,
                         n_layer=2, n_head=2, dtype=jnp.bfloat16,
                         loss_chunk=chunk)
        model = GPT2LMHead(cfg)
        params = init_gpt2_params(model, jax.random.PRNGKey(0), seq_len=32)
        config = {"train_batch_size": 8,
                  "bf16": {"enabled": True},
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                  "steps_per_print": 1000}
        if zero_stage:
            config["zero_optimization"] = {"stage": zero_stage}
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=config, loss_fn=make_gpt2_loss_fn(model), params=params)
        batch = {"input_ids": np.random.default_rng(0).integers(
            0, 128, (8, 32)).astype(np.int32)}
        return [float(engine.train_batch(batch)) for _ in range(5)]

    chunked_z3, chunked_z0 = train(8, 3), train(8, 0)
    dense_z3 = train(0, 3)
    # ZeRO-3 sharding must not change the chunked curve at all.
    np.testing.assert_allclose(chunked_z3, chunked_z0, rtol=1e-6)
    # Chunked vs dense: fp32-accumulated head cotangent (see docstring).
    np.testing.assert_allclose(chunked_z3, dense_z3, rtol=2e-4)
