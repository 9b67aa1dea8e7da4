"""The plain reference against LlamaForCausalLM at a tiny size: GQA 4/2,
a sliding window shorter than the sequence, float32 on both sides."""
import numpy as np
import pytest

from benchmark.reference import decoder as ref

MODEL = dict(vocab_size=97, hidden_size=64, intermediate_size=160,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, max_position_embeddings=64,
             rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=5,
             tie_word_embeddings=False)


@pytest.fixture(scope="module")
def net():
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM
    keys = {k: v for k, v in MODEL.items() if k != "rope_theta"}
    paddle.seed(11)
    return LlamaForCausalLM(LlamaConfig(**keys, use_flash_attention=True,
                                        fused_linear_ce=True,
                                        fused_ce_chunks=2))


def _system(net, ids, labels=None):
    import paddle_tpu as paddle
    with paddle.no_grad():
        out = net(paddle.to_tensor(ids[None]),
                  labels=None if labels is None
                  else paddle.to_tensor(labels[None]))
    return np.asarray(out.numpy())


def test_logits_and_loss_agree(net):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, MODEL["vocab_size"], 24).astype(np.int64)
    labels = rng.integers(0, MODEL["vocab_size"], 24).astype(np.int64)
    want = ref.logits(ref.model_weights(net), MODEL, ids)
    got = _system(net, ids)[0]
    # float32 on both sides: only the order of summation differs
    assert ref.max_normalised_error(got, want) < 1e-4
    loss = float(_system(net, ids, labels))
    assert abs(loss - float(ref.mean_cross_entropy(want, labels))) < 1e-4


def test_the_window_bites(net):
    """The same weights without the window give other logits once the
    sequence is longer than it, and the same logits before that: the
    agreement above is not blind to the mask."""
    rng = np.random.default_rng(1)
    ids = rng.integers(0, MODEL["vocab_size"], 24).astype(np.int64)
    w = ref.model_weights(net)
    windowed = np.asarray(ref.logits(w, MODEL, ids))
    full = np.asarray(ref.logits(w, dict(MODEL, sliding_window=None), ids))
    n = MODEL["sliding_window"]
    np.testing.assert_allclose(windowed[:n], full[:n], atol=1e-5)
    assert ref.max_normalised_error(windowed[n:], full[n:]) > 1e-3


def test_rotary_layout_matters(net):
    """A reference with theta changed disagrees with the system."""
    rng = np.random.default_rng(2)
    ids = rng.integers(0, MODEL["vocab_size"], 24).astype(np.int64)
    wrong = ref.logits(ref.model_weights(net), dict(MODEL, rope_theta=500.0),
                       ids)
    assert ref.max_normalised_error(_system(net, ids)[0], wrong) > 1e-3
