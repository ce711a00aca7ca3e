"""Full-geometry parity hardening (BASELINE logit-parity
row): random-weight logits parity vs HF transformers at the EXACT Oryx-7B
backbone width — hidden 3584, 28 q / 4 kv heads (group 7), head_dim 128,
vocab 152064, Qwen2 attention bias — at reduced depth (2 layers), plus a
bf16-vs-fp32 drift bound at the same width.

Tolerances are pinned from measurement on this geometry (fp32 max abs
2.0e-5; bf16 max log-prob drift 0.102, top-1 agreement 1.0) with ~2-10x
headroom.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import import_hf, qwen2

CFG = dataclasses.replace(cfg_lib.qwen2_7b(), num_layers=2)


@pytest.fixture(scope="module")
def seven_b(  # noqa: C901 - fixture builds both frameworks' models once
):
    torch = pytest.importorskip("torch")
    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(0)
    hf_cfg = Qwen2Config(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_layers,
        num_attention_heads=CFG.num_heads,
        num_key_value_heads=CFG.num_kv_heads,
        head_dim=CFG.head_dim,
        rope_theta=CFG.rope_theta,
        rms_norm_eps=CFG.rms_norm_eps,
        max_position_embeddings=CFG.max_position_embeddings,
        tie_word_embeddings=False,
        attention_dropout=0.0,
    )
    model = Qwen2ForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, CFG.vocab_size, size=(1, 9))
    with torch.no_grad():
        ref = model(torch.tensor(ids)).logits.numpy()
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    del model
    jx = import_hf.import_qwen2(sd, CFG)
    del sd
    return ids, ref, jx


@pytest.mark.slow
def test_logits_parity_7b_width(seven_b):
    ids, ref, jx = seven_b
    got, _ = qwen2.forward(jx, CFG, input_ids=jnp.asarray(ids))
    np.testing.assert_allclose(
        np.asarray(got), ref, atol=2e-4, rtol=2e-3
    )


@pytest.mark.slow
def test_bf16_drift_bound_7b_width(seven_b):
    """bf16 compute must stay within a bounded drift of fp32: log-prob
    max-abs < 0.25 and >= 99% greedy-token agreement."""
    ids, _, jx = seven_b
    got32, _ = qwen2.forward(jx, CFG, input_ids=jnp.asarray(ids))
    gotbf, _ = qwen2.forward(
        jx, CFG, input_ids=jnp.asarray(ids), compute_dtype=jnp.bfloat16
    )
    lg32 = np.asarray(jax.nn.log_softmax(got32))
    lgbf = np.asarray(jax.nn.log_softmax(gotbf.astype(jnp.float32)))
    assert np.abs(lgbf - lg32).max() < 0.25
    agree = (
        np.asarray(gotbf).argmax(-1) == np.asarray(got32).argmax(-1)
    ).mean()
    assert agree >= 0.99
