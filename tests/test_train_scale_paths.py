"""Paths that make billion-parameter single-chip training fit (the
`gpt3-1.3b.train-2k` cell): per-block remat, bf16 AdamW moments, AMP over raw
batch inputs, conv autodiff under autocast, deepcopy buffer ownership.

Ref test strategy: test/collective/fleet/ recompute + AMP payloads
(SURVEY §4)."""
import copy

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import amp
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import GPTConfig, gpt_tiny
from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
from paddle_tpu.optimizer import AdamW, Momentum
import paddle_tpu.ops as ops


def _tiny_cfg(**kw):
    return GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                     num_heads=4, max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     **kw)


class TestRecompute:
    def test_gpt_recompute_matches_plain(self):
        """config.recompute re-runs block forwards in backward — same
        loss AND same grads as the plain path."""
        ids = np.random.RandomState(0).randint(0, 512, (2, 64)).astype(
            np.int32)
        labels = np.random.RandomState(1).randint(0, 512, (2, 64)).astype(
            np.int32)
        results = []
        for rc in (False, True):
            paddle.seed(7)
            m = GPTForCausalLM(_tiny_cfg(recompute=rc))
            m.train()
            crit = GPTPretrainingCriterion()
            loss = crit(m(paddle.to_tensor(ids)), paddle.to_tensor(labels))
            loss.backward()
            g = m.gpt.layers[0].mlp.fc1.weight.grad.numpy()
            results.append((float(loss.numpy()), g))
        np.testing.assert_allclose(results[0][0], results[1][0], rtol=1e-5)
        np.testing.assert_allclose(results[0][1], results[1][1],
                                   rtol=1e-4, atol=1e-5)

    def test_recompute_under_trainstep(self):
        paddle.seed(3)
        m = GPTForCausalLM(_tiny_cfg(recompute=True))
        m.train()
        opt = AdamW(learning_rate=1e-3, parameters=m.parameters())
        crit = GPTPretrainingCriterion()

        def loss_fn(mm, ids, labels):
            with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
                logits = mm(ids)
            return crit(logits, labels)

        step = TrainStep(m, opt, loss_fn)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 512, (2, 64)).astype(np.int32)
        labels = rng.integers(0, 512, (2, 64)).astype(np.int32)
        l0 = float(step(ids, labels).numpy())
        for _ in range(4):
            loss = step(ids, labels)
        assert float(loss.numpy()) < l0  # trains


class TestMomentDtype:
    def test_bf16_moments_dtype_and_convergence(self):
        """AdamW(moment_dtype='bfloat16') stores m/v in bf16 (half the
        optimizer-state HBM) and still optimizes."""
        paddle.seed(11)
        lin = paddle.nn.Linear(16, 4)
        opt = AdamW(learning_rate=0.05, parameters=lin.parameters(),
                    moment_dtype="bfloat16")
        rng = np.random.default_rng(2)
        x = paddle.to_tensor(rng.standard_normal((32, 16)).astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((32, 4)).astype(np.float32))
        losses = []
        for _ in range(30):
            loss = ((lin(x) - y) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        st = opt._get_state(lin.weight)
        assert str(st["moment1"].dtype) == "bfloat16"
        assert str(st["moment2"].dtype) == "bfloat16"
        assert losses[-1] < 0.5 * losses[0]

    def test_bf16_moments_track_f32(self):
        """Short-horizon updates with bf16 moments stay close to f32."""
        rng = np.random.default_rng(3)
        x = rng.standard_normal((8, 8)).astype(np.float32)
        params = {}
        for mdt in (None, "bfloat16"):
            paddle.seed(5)
            lin = paddle.nn.Linear(8, 8)
            opt = AdamW(learning_rate=1e-2, parameters=lin.parameters(),
                        moment_dtype=mdt)
            xt = paddle.to_tensor(x)
            for _ in range(3):
                loss = (lin(xt) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
            params[mdt] = lin.weight.numpy()
        np.testing.assert_allclose(params[None], params["bfloat16"],
                                   rtol=2e-2, atol=2e-3)


class TestDeepcopyBuffers:
    def test_deepcopy_params_own_buffers(self):
        """Deep-copied layers (TransformerEncoder stacking) must own
        distinct device buffers — XLA rejects donating one buffer twice."""
        lin = paddle.nn.Linear(8, 8)
        lin2 = copy.deepcopy(lin)
        w1, w2 = lin.weight._data, lin2.weight._data
        if hasattr(w1, "unsafe_buffer_pointer"):
            assert (w1.unsafe_buffer_pointer()
                    != w2.unsafe_buffer_pointer())
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))

    def test_encoder_stack_trains_under_trainstep(self):
        """The BERT-bench shape: deep-copied encoder layers + donation."""
        from paddle_tpu.models.bert import BertConfig, BertForMaskedLM
        cfg = BertConfig(vocab_size=256, hidden_size=64, num_layers=2,
                         num_heads=2, intermediate_size=128,
                         max_position_embeddings=64,
                         hidden_dropout_prob=0.0,
                         attention_dropout_prob=0.0)
        m = BertForMaskedLM(cfg)
        m.train()
        opt = AdamW(learning_rate=1e-3, parameters=m.parameters())

        def loss_fn(mm, ids, labels):
            with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
                loss, _ = mm(ids, labels=labels)
            return loss

        step = TrainStep(m, opt, loss_fn)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 256, (2, 32)).astype(np.int32)
        labels = np.where(rng.random((2, 32)) < 0.15, ids, -100).astype(
            np.int32)
        loss = step(ids, labels)
        assert np.isfinite(float(loss.numpy()))


class TestConvAmpTrainStep:
    def test_conv_bn_trains_under_autocast(self):
        """ResNet-bench shape: raw f32 batch arrays are cast by autocast
        inside the trace, and conv autodiff works in bf16 (no
        preferred_element_type dtype clash in the transpose rule)."""
        paddle.seed(9)
        m = paddle.nn.Sequential(
            paddle.nn.Conv2D(3, 8, 3, padding=1),
            paddle.nn.BatchNorm2D(8),
            paddle.nn.ReLU(),
            paddle.nn.Flatten(),
            paddle.nn.Linear(8 * 16 * 16, 10),
        )
        m.train()
        opt = Momentum(learning_rate=0.05, momentum=0.9,
                       parameters=m.parameters())

        def loss_fn(mm, x, y):
            with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
                logits = mm(x)
            return ops.cross_entropy(logits, y)

        step = TrainStep(m, opt, loss_fn)
        rng = np.random.default_rng(1)
        x = rng.standard_normal((4, 3, 16, 16)).astype(np.float32)
        y = rng.integers(0, 10, (4,)).astype(np.int32)
        l0 = float(step(x, y).numpy())
        for _ in range(5):
            loss = step(x, y)
        assert float(loss.numpy()) < l0
