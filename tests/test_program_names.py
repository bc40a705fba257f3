"""Names the program chooses: ``name=`` on the Pallas calls and
``jax.named_scope`` on the regions of the two step bodies, as a device
trace will carry them (PERF.md section 3), and the module names the
benchmark matches (benchmarks/lib/names.py), which must not move."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.serving import DecodeEngine
from test_paged_decode import CFG, _decoder, _model


def _has(text: str, name: str) -> bool:
    """``name`` as one component of an op's location path, bare or
    inside a transformation's wrapper (``transpose(jvp(name))``)."""
    return re.search(r'[/"(]%s[/")]' % re.escape(name), text) is not None


@pytest.fixture(scope="module")
def decode_step_text():
    dec = _decoder(_model())
    eng = DecodeEngine(dec, num_slots=2, page_size=4,
                       max_seq_len=CFG["max_len"], attention="kernel")
    z = jnp.zeros((2, 1), jnp.int32)
    args = (dec.p, eng.k_pool, eng.v_pool, z, z, jnp.asarray(eng._tables),
            jnp.zeros((2, 1), jnp.bool_), jax.random.PRNGKey(0))
    return eng.paged._step.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("name", ["embed", "kv_write", "paged_attn", "ffn",
                                  "logits", "paged_window_attention"])
def test_decode_step_carries_the_name(decode_step_text, name):
    assert _has(decode_step_text, name)


def test_decode_step_module_name_is_what_the_benchmark_matches(
        decode_step_text):
    assert "module @jit__step_impl" in decode_step_text


@pytest.fixture(scope="module")
def latent_step_text():
    """The step of a latent (MLA) block with expert layers
    (models/block.py), on the kernel path."""
    from test_latent_decode import CFG as KCFG, MODEL, REF, SEED
    from paddle_tpu import models
    named = MODEL.make_weights(REF, SEED, KCFG, jnp.float32)
    dec = models.TransformerDecoder(
        named, n_layers=KCFG["num_hidden_layers"],
        n_heads=KCFG["num_attention_heads"], name=MODEL.NAME,
        block=MODEL.block_of(KCFG, 64))
    eng = DecodeEngine(dec, num_slots=2, page_size=4, max_seq_len=32,
                       attention="kernel")
    z = jnp.zeros((2, 1), jnp.int32)
    args = (dec.p, eng.k_pool, eng.v_pool, z, z, jnp.asarray(eng._tables),
            jnp.zeros((2, 1), jnp.bool_), jax.random.PRNGKey(0))
    return eng.paged._step.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("name", [
    "embed", "latent_kv_write", "latent_attn", "ffn", "router", "experts",
    "shared_expert", "logits", "paged_latent_attention"])
def test_latent_step_carries_the_name(latent_step_text, name):
    assert _has(latent_step_text, name)


def test_latent_step_is_the_module_the_benchmark_matches(latent_step_text):
    assert "module @jit__step_impl" in latent_step_text


@pytest.fixture(scope="module")
def state_step_text():
    """The step of a block with a recurrent state beside its latent
    pages (models/block.py DeltaLatentBlock), on the kernel path."""
    from test_state_decode import CFG as LCFG, MODEL, REF, SEED
    from paddle_tpu import models
    named = MODEL.make_weights(REF, SEED, LCFG, jnp.float32)
    dec = models.TransformerDecoder(
        named, n_layers=LCFG["num_hidden_layers"],
        n_heads=LCFG["num_attention_heads"], name=MODEL.NAME,
        block=MODEL.block_of(LCFG, 64))
    eng = DecodeEngine(dec, num_slots=2, page_size=4, max_seq_len=32,
                       attention="kernel", state_snapshots=2)
    z = jnp.zeros((2, 1), jnp.int32)
    args = (dec.p, eng.k_pool, eng.v_pool, z, z, jnp.asarray(eng._tables),
            jnp.zeros((2, 1), jnp.bool_), jax.random.PRNGKey(0))
    return eng.paged._step.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("name", [
    "embed", "kda_conv", "kda_short_conv", "kda_gates", "kda_state",
    "kda_state_update",
    "latent_kv_write", "latent_attn", "paged_latent_attention", "ffn",
    "router", "experts", "shared_expert", "logits"])
def test_state_step_carries_the_name(state_step_text, name):
    assert _has(state_step_text, name)


def test_state_step_is_the_module_the_benchmark_matches(state_step_text):
    assert "module @jit__step_impl" in state_step_text


@pytest.fixture(scope="module")
def conv_step_text():
    """The step of a block of gated short convolutions beside per-head
    pages (models/block.py ShortConvBlock), on the kernel path."""
    from test_conv_decode import CFG as CCFG, MODEL, REF, SEED
    from paddle_tpu import models
    named = MODEL.make_weights(REF, SEED, CCFG, jnp.float32)
    dec = models.TransformerDecoder(
        named, n_layers=CCFG["num_hidden_layers"],
        n_heads=CCFG["num_attention_heads"], name=MODEL.NAME,
        block=MODEL.block_of(CCFG, 64))
    eng = DecodeEngine(dec, num_slots=2, page_size=4, max_seq_len=32,
                       attention="kernel", state_snapshots=2)
    z = jnp.zeros((2, 1), jnp.int32)
    args = (dec.p, eng.k_pool, eng.v_pool, z, z, jnp.asarray(eng._tables),
            jnp.zeros((2, 1), jnp.bool_), jax.random.PRNGKey(0))
    return eng.paged._step.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("name", [
    "embed", "conv_gates", "short_conv", "qk_norm_rope", "kv_write",
    "paged_attn", "paged_window_attention", "ffn", "router", "experts",
    "logits"])
def test_conv_step_carries_the_name(conv_step_text, name):
    assert _has(conv_step_text, name)


def test_conv_step_has_no_shared_expert_and_is_the_module_matched(
        conv_step_text):
    assert not _has(conv_step_text, "shared_expert")
    assert not _has(conv_step_text, "kda_short_conv")
    assert "module @jit__step_impl" in conv_step_text


@pytest.fixture(scope="module")
def train_step_text():
    from benchmarks.lib import manifest, paddle_lm
    cfg = {"hidden_size": 32, "num_hidden_layers": 2,
           "num_attention_heads": 4, "ffn_dim": 64, "vocab_size": 64,
           "max_position_embeddings": 32}
    ref = manifest.load_module("reference", "opt")
    named = paddle_lm.make_weights(ref, 7, cfg, jnp.float32)
    trainer = paddle_lm.build_trainer(
        named, cfg, {"learning_rate": 1e-4, "compute_dtype": "float32"},
        1, False)
    from paddle_tpu.trainer.data_feeder import DataFeeder
    batch = np.random.default_rng(1).integers(0, 64, (4, 33)).astype(np.int32)
    feed = DataFeeder(trainer.topology.data_type(), None)(
        paddle_lm.rows_of(batch))
    n_real = jnp.asarray(feed.pop("__batch_size__"), jnp.int32)
    return trainer._train_step.lower(
        trainer._own_params(), trainer.opt_state, trainer.parameters.state,
        feed, jax.random.PRNGKey(0), n_real).as_text(debug_info=True)


@pytest.mark.parametrize("name", ["loss_and_grad", "optimizer"])
def test_train_step_carries_the_scope(train_step_text, name):
    assert _has(train_step_text, name)


def test_train_step_module_name_is_what_the_benchmark_matches(
        train_step_text):
    assert "module @jit_step" in train_step_text


@pytest.fixture(scope="module")
def flash_text():
    from paddle_tpu.ops.pallas_attention import flash_attention
    x = jnp.zeros((1, 16, 2, 8), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).as_text(debug_info=True)


@pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd_dq",
                                  "flash_bwd_dkv"])
def test_flash_kernels_carry_their_names(flash_text, name):
    assert _has(flash_text, name)


def test_every_pallas_call_of_the_kernel_files_is_named():
    """No mix: a ``pl.pallas_call`` without ``name=`` reads in a trace
    under whatever the tracer of autodiff leaves."""
    import inspect
    from paddle_tpu.ops import (pallas_attention, pallas_decode, pallas_kda,
                                pallas_rnn)
    for mod in (pallas_attention, pallas_decode, pallas_kda, pallas_rnn):
        src = inspect.getsource(mod)
        calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(", src)]
        assert calls, mod.__name__
        for a, b in zip(calls, calls[1:] + [len(src)]):
            assert re.search(r'\bname="\w+"', src[a:b]), \
                (mod.__name__, src[a:a + 80])
