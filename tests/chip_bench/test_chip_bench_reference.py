"""The benchmark's plain float32 Qwen3 reference against the program's
``models/`` at a small size on the CPU, and the benchmark's inputs against
the program's own initialiser and data pipeline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import arch, inputs
from benchmarks.chip.reference import qwen3 as ref
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import zoo

CFG = {"model_type": "qwen3", "hidden_size": 64, "intermediate_size": 96,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "vocab_size": 256, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
       "rope_theta": 1_000_000, "attention_bias": False, "tie_word_embeddings": True,
       "max_position_embeddings": 4096, "torch_dtype": "float32", "hidden_act": "silu"}
KEY = inputs.arch_key(CFG)


@pytest.fixture(scope="module")
def model():
    return zoo.build(arch.arch_config("tiny", CFG), dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return inputs.make_params(CFG, 3, jnp.float32)


def _batch(seed=3, batch=2, seq=16):
    return inputs.train_rows(0, batch=batch, seq_len=seq, vocab=CFG["vocab_size"],
                             seed=seed, mean_doc_len=8)


def test_weights_follow_the_programs_initialiser(model):
    for dtype in (jnp.float32, jnp.bfloat16):
        mine = inputs.make_params(CFG, 11, dtype)
        theirs = jax.jit(zoo.build(model.cfg, dtype=dtype).init)(jax.random.PRNGKey(11))
        assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
        for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_rows_follow_the_programs_pipeline():
    for step in (0, 5):
        want = SyntheticLM(DataConfig(vocab_size=256, seq_len=32, global_batch=4, seed=9,
                                      mean_doc_len=8)).batch_at(step)
        got = inputs.train_rows(step, batch=4, seq_len=32, vocab=256, seed=9, mean_doc_len=8)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])


def test_forward_matches_models(model, params):
    tokens = _batch()["tokens"]
    want, _ = jax.jit(model.forward)(params, {"tokens": jnp.asarray(tokens)})
    for b in range(tokens.shape[0]):
        h = ref.hidden(CFG, params, jnp.asarray(tokens[b]))
        got = ref.logits(CFG, params, h)
        np.testing.assert_allclose(got, want[b], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("replicas", [1, 2])
def test_loss_and_gradient_match_models(model, params, replicas):
    batch = _batch(batch=4)
    per = 4 // replicas

    def program_loss(p):  # the data-parallel mean of per-replica losses
        losses = [model.loss(p, {k: jnp.asarray(v[r * per:(r + 1) * per]) for k, v in batch.items()})[0]
                  for r in range(replicas)]
        return sum(losses) / replicas

    want_loss, want_grad = jax.jit(jax.value_and_grad(program_loss))(params)
    loss, grad = ref.loss_and_grad(KEY, params, jax.tree_util.tree_map(jnp.asarray, batch),
                                   replicas, False)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(grad), jax.tree_util.tree_leaves(want_grad)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-6)


def test_prefill_and_decode_through_the_cache_match(model, params):
    prompt = inputs.prompts(0, batch=2, prompt_len=8, vocab=CFG["vocab_size"], seed=3)
    logits, cache = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, 16))(params, jnp.asarray(prompt))
    served, outs = [], [logits]
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, {"tokens": t}))
    for _ in range(5):
        tok = jnp.argmax(outs[-1], axis=-1).astype(jnp.int32)
        served.append(np.asarray(tok))
        logits, cache = step(params, cache, tok[:, None])
        outs.append(logits)
    served = np.stack(served, axis=1)
    for b in range(2):
        seq = jnp.asarray(np.concatenate([prompt[b], served[b, :-1]]), jnp.int32)
        got = ref.served_logits(KEY, params, seq, 7, False)
        np.testing.assert_allclose(got, np.stack([o[b] for o in outs[:5]]), rtol=2e-4, atol=2e-4)


def test_adamw_follows_the_schedule():
    opt = {"lr": 1e-3, "warmup_steps": 2, "total_steps": 10, "min_lr_ratio": 0.1}
    assert ref.lr_at(opt, 1) == pytest.approx(5e-4)
    assert ref.lr_at(opt, 2) == pytest.approx(1e-3)
    assert ref.lr_at(opt, 10) == pytest.approx(1e-4)
