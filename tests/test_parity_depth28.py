"""FULL-DEPTH parity leg (SURVEY.md §4 "Unit",
§7 hard part 2): random-weight logits parity vs HF transformers at the
Oryx-7B backbone's exact DEPTH (num_layers=28) with head_dim 128, GQA
group 7, vocab 152064 and Qwen2 attention bias kept — width reduced to
hidden 896 (7 q / 1 kv heads, intermediate 4736, ~0.68 B params) so the
test fits CI on a 1-core box (~90 s vs ~8 min at half width).

Complements tests/test_parity_7b.py, which pins the exact 7B WIDTH at
depth 2: between them both axes of the geometry are covered, so
depth-compounded drift can no longer hide behind the shallow test.

Tolerances pinned from measurement on this box (2026-07-30):
  - this geometry (896 x 28L):  fp32 max abs 5.25e-6; bf16 log-prob max
    drift 0.0704; greedy top-1 agreement 1.0
  - half 7B width (1792 x 28L, 14q/2kv, ~2.2 B params): fp32 max abs
    1.76e-5; bf16 log-prob drift 0.1324; top-1 agreement 1.0
Bounds below carry ~3-4x headroom over the measured values.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import import_hf, qwen2

CFG = dataclasses.replace(
    cfg_lib.qwen2_7b(),
    num_layers=28,
    hidden_size=896,
    intermediate_size=4736,
    num_heads=7,
    num_kv_heads=1,
)


@pytest.fixture(scope="module")
def depth28():
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
def test_logits_parity_depth28(depth28):
    ids, ref, jx = depth28
    got, _ = qwen2.forward(jx, CFG, input_ids=jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), ref, atol=2e-5, rtol=2e-3)


@pytest.mark.slow
def test_bf16_drift_bound_depth28(depth28):
    """28 layers of bf16 compute must stay within a bounded drift of the
    fp32 path: log-prob max-abs < 0.2 and >= 99% greedy agreement."""
    ids, _, jx = depth28
    got32, _ = qwen2.forward(jx, CFG, input_ids=jnp.asarray(ids))
    gotbf, _ = qwen2.forward(
        jx, CFG, input_ids=jnp.asarray(ids), compute_dtype=jnp.bfloat16
    )
    lg32 = np.asarray(jax.nn.log_softmax(got32))
    lgbf = np.asarray(jax.nn.log_softmax(gotbf.astype(jnp.float32)))
    assert np.abs(lgbf - lg32).max() < 0.2
    agree = (
        np.asarray(gotbf).argmax(-1) == np.asarray(got32).argmax(-1)
    ).mean()
    assert agree >= 0.99
