"""Tests for paddle.vision.ops (detection ops) and channels-last layouts of
the model zoo (its train steps: ``test_model_zoo_train.py``)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.vision import ops as V


def t(x):
    return paddle.to_tensor(np.asarray(x))


class TestNMS:
    def test_basic(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11], [20, 20, 30, 30]],
                         np.float32)
        scores = np.array([0.9, 0.8, 0.7], np.float32)
        kept = np.asarray(V.nms(t(boxes), 0.5, t(scores))._data)
        np.testing.assert_array_equal(kept, [0, 2])

    def test_no_scores_keeps_input_order(self):
        boxes = np.array([[0, 0, 10, 10], [0, 0, 10, 10], [50, 0, 60, 10]],
                         np.float32)
        kept = np.asarray(V.nms(t(boxes), 0.5)._data)
        np.testing.assert_array_equal(kept, [0, 2])

    def test_categorical(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11], [0, 0, 10, 10]],
                         np.float32)
        scores = np.array([0.9, 0.8, 0.95], np.float32)
        cats = np.array([0, 0, 1])
        kept = np.asarray(V.nms(t(boxes), 0.5, t(scores), t(cats),
                                categories=[0, 1])._data)
        # cat 0: box1 suppressed by box0; cat 1: box2 kept; sorted by score
        np.testing.assert_array_equal(sorted(kept.tolist()), [0, 2])
        assert kept[0] == 2  # highest score first


class TestRoIAlign:
    def test_whole_image_box_on_linear_ramp(self):
        # on a linear ramp, symmetric samples average to the box-center
        # value: box [0,4]² centered at (2,2) -> x[2,2] = 10
        x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
        boxes = np.array([[0, 0, 4, 4]], np.float32)
        out = np.asarray(V.roi_align(t(x), t(boxes), t(np.array([1])),
                                     output_size=1, sampling_ratio=1,
                                     aligned=False)._data)
        np.testing.assert_allclose(out[0, 0, 0, 0], 10.0, atol=1e-5)

    def test_half_scale_and_grad(self):
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(1, 2, 8, 8).astype(np.float32),
            stop_gradient=False)
        boxes = t(np.array([[0, 0, 8, 8], [2, 2, 6, 6]], np.float32))
        out = V.roi_align(x, boxes, t(np.array([2])), output_size=2)
        assert tuple(out.shape) == (2, 2, 2, 2)
        paddle.mean(out).backward()
        g = np.asarray(x.grad._data)
        assert np.isfinite(g).all() and np.abs(g).sum() > 0

    def test_roi_pool_whole_image_is_global_max(self):
        x = np.random.RandomState(1).randn(1, 3, 6, 6).astype(np.float32)
        boxes = np.array([[0, 0, 5, 5]], np.float32)
        out = np.asarray(V.roi_pool(t(x), t(boxes), t(np.array([1])),
                                    output_size=1)._data)
        np.testing.assert_allclose(out[0, :, 0, 0], x[0].max(axis=(1, 2)),
                                   rtol=1e-5)

    def test_psroi_pool_constant_channels(self):
        # C = out_c(2) * 2*2; constant per channel -> each bin returns the
        # constant of its own channel slice
        vals = np.arange(8, dtype=np.float32)
        x = np.broadcast_to(vals[None, :, None, None], (1, 8, 6, 6)).copy()
        boxes = np.array([[0, 0, 6, 6]], np.float32)
        out = np.asarray(V.psroi_pool(t(x), t(boxes), t(np.array([1])),
                                      output_size=2)._data)
        assert tuple(out.shape) == (1, 2, 2, 2)
        np.testing.assert_allclose(out[0, 0].reshape(-1), vals[:4])
        np.testing.assert_allclose(out[0, 1].reshape(-1), vals[4:])

    def test_layers(self):
        x = t(np.random.randn(1, 4, 8, 8).astype(np.float32))
        boxes = t(np.array([[0, 0, 8, 8]], np.float32))
        bn = t(np.array([1]))
        assert tuple(V.RoIAlign(2)(x, boxes, bn).shape) == (1, 4, 2, 2)
        assert tuple(V.RoIPool(2)(x, boxes, bn).shape) == (1, 4, 2, 2)
        assert tuple(V.PSRoIPool(2, 1.0)(x, boxes, bn).shape) == (1, 1, 2, 2)


class TestDeformConv:
    def test_zero_offset_equals_conv(self):
        rng = np.random.RandomState(2)
        x = rng.randn(2, 4, 8, 8).astype(np.float32)
        w = rng.randn(6, 4, 3, 3).astype(np.float32)
        offset = np.zeros((2, 2 * 9, 6, 6), np.float32)
        ours = np.asarray(V.deform_conv2d(t(x), t(offset), t(w))._data)
        ref = np.asarray(F.conv2d(t(x), t(w))._data)
        np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)

    def test_zero_offset_stride_pad(self):
        rng = np.random.RandomState(3)
        x = rng.randn(1, 2, 9, 9).astype(np.float32)
        w = rng.randn(4, 2, 3, 3).astype(np.float32)
        offset = np.zeros((1, 18, 5, 5), np.float32)
        ours = np.asarray(V.deform_conv2d(t(x), t(offset), t(w), stride=2,
                                          padding=1)._data)
        ref = np.asarray(F.conv2d(t(x), t(w), stride=2, padding=1)._data)
        np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)

    def test_integer_offset_shifts_input(self):
        # 1x1 kernel with offset (+1, +1) == sampling x[..., i+1, j+1]
        rng = np.random.RandomState(4)
        x = rng.randn(1, 1, 5, 5).astype(np.float32)
        w = np.ones((1, 1, 1, 1), np.float32)
        offset = np.ones((1, 2, 5, 5), np.float32)
        out = np.asarray(V.deform_conv2d(t(x), t(offset), t(w))._data)
        np.testing.assert_allclose(out[0, 0, :4, :4], x[0, 0, 1:, 1:],
                                   rtol=1e-5)

    def test_mask_modulates(self):
        rng = np.random.RandomState(5)
        x = rng.randn(1, 2, 6, 6).astype(np.float32)
        w = rng.randn(3, 2, 3, 3).astype(np.float32)
        offset = np.zeros((1, 18, 4, 4), np.float32)
        ones = np.ones((1, 9, 4, 4), np.float32)
        out1 = np.asarray(V.deform_conv2d(t(x), t(offset), t(w),
                                          mask=t(ones))._data)
        ref = np.asarray(F.conv2d(t(x), t(w))._data)
        np.testing.assert_allclose(out1, ref, rtol=1e-4, atol=1e-4)
        out0 = np.asarray(V.deform_conv2d(t(x), t(offset), t(w),
                                          mask=t(0 * ones))._data)
        np.testing.assert_allclose(out0, 0.0, atol=1e-6)

    def test_layer_trains(self):
        layer = V.DeformConv2D(2, 4, 3, padding=1)
        x = paddle.to_tensor(np.random.randn(1, 2, 6, 6).astype(np.float32),
                             stop_gradient=False)
        offset = paddle.to_tensor(
            0.1 * np.random.randn(1, 18, 6, 6).astype(np.float32),
            stop_gradient=False)
        out = layer(x, offset)
        paddle.mean(out).backward()
        assert np.abs(np.asarray(layer.weight.grad._data)).sum() > 0
        assert np.abs(np.asarray(offset.grad._data)).sum() > 0


class TestYolo:
    def test_yolo_box_decode_zeros(self):
        # zero logits: sigmoid=0.5 -> centers at (grid+0.5)/size, w=anchor/in
        n, na, cls, h, w = 1, 2, 3, 2, 2
        x = np.zeros((n, na * (5 + cls), h, w), np.float32)
        img = np.array([[64, 64]], np.int32)
        boxes, scores = V.yolo_box(t(x), t(img), anchors=[10, 14, 23, 27],
                                   class_num=cls, downsample_ratio=32)
        b = np.asarray(boxes._data)
        s = np.asarray(scores._data)
        assert b.shape == (1, na * h * w, 4) and s.shape == (1, na * h * w,
                                                             cls)
        # first box: center (16,16); anchor0 = (w=10, h=14)
        np.testing.assert_allclose(b[0, 0], [11, 9, 21, 23], atol=1e-4)
        # conf=0.5 > thresh; score = 0.5*0.5
        np.testing.assert_allclose(s[0, 0], 0.25, atol=1e-5)

    def test_yolo_loss_grad_and_ordering(self):
        rng = np.random.RandomState(6)
        n, cls, h = 1, 3, 4
        anchors = [10, 13, 16, 30, 33, 23]
        mask = [0, 1, 2]
        x = paddle.to_tensor(
            0.1 * rng.randn(n, 3 * (5 + cls), h, h).astype(np.float32),
            stop_gradient=False)
        gt_box = t(np.array([[[0.5, 0.5, 0.2, 0.3]]], np.float32))
        gt_label = t(np.array([[1]], np.int32))
        loss = V.yolo_loss(x, gt_box, gt_label, anchors, mask, cls,
                           ignore_thresh=0.7, downsample_ratio=8)
        loss_v = float(paddle.mean(loss))
        assert np.isfinite(loss_v) and loss_v > 0
        paddle.mean(loss).backward()
        assert np.abs(np.asarray(x.grad._data)).sum() > 0


class TestChannelsLast:
    """r3 verdict item 3: NHWC (channels-last) is the TPU-preferred conv
    layout; the resnet family threads data_format end to end and NHWC
    weights stay OIHW so checkpoints are layout-interchangeable. Also pins
    the conv dimension-numbers fix (weights were mis-declared HWIO)."""

    def test_conv2d_nhwc_matches_nchw(self):
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(0)
        x = rng.randn(2, 3, 8, 8).astype("float32")
        w = rng.randn(16, 3, 3, 3).astype("float32")
        b = rng.randn(16).astype("float32")
        out = F.conv2d(paddle.to_tensor(x), paddle.to_tensor(w),
                       paddle.to_tensor(b), stride=2, padding=1)
        out_cl = F.conv2d(paddle.to_tensor(x.transpose(0, 2, 3, 1)),
                          paddle.to_tensor(w), paddle.to_tensor(b),
                          stride=2, padding=1, data_format="NHWC")
        np.testing.assert_allclose(
            out.numpy(), out_cl.numpy().transpose(0, 3, 1, 2),
            rtol=1e-4, atol=1e-5)

    def test_resnet18_nhwc_logits_match_nchw(self):
        from paddle_tpu.vision.models import resnet18
        paddle.framework.random.seed(0)
        m = resnet18(num_classes=10)
        m_cl = resnet18(num_classes=10, data_format="NHWC")
        m_cl.set_state_dict(m.state_dict())  # OIHW weights in both
        m.eval()
        m_cl.eval()
        x = np.random.RandomState(0).randn(2, 3, 64, 64).astype("float32")
        y = m(paddle.to_tensor(x)).numpy()
        y_cl = m_cl(paddle.to_tensor(
            x.transpose(0, 2, 3, 1))).numpy()
        np.testing.assert_allclose(y, y_cl, rtol=1e-3, atol=1e-4)

    def test_resnet_nhwc_trains(self):
        from paddle_tpu.vision.models import resnet18
        m = resnet18(num_classes=4, data_format="NHWC")
        opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                        parameters=m.parameters())
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 32, 32, 3).astype(
                "float32"))
        y = paddle.to_tensor(np.array([[1], [2]], "int64"))
        loss = paddle.nn.CrossEntropyLoss()(m(x), y)
        loss.backward()
        opt.step()
        assert np.isfinite(float(loss.numpy()))

    def test_bad_data_format_rejected(self):
        from paddle_tpu.vision.models import resnet18
        with pytest.raises(ValueError):
            resnet18(data_format="NWHC")


def test_mobilenet_nhwc_matches_nchw():
    """Channels-last MobileNet (TPU layout for depthwise convs) matches
    NCHW numerically — weights stay OIHW so one checkpoint serves both."""
    import numpy as np
    from paddle_tpu.vision.models import MobileNetV2

    paddle.framework.random.seed(0)
    a = MobileNetV2(scale=0.25, num_classes=7)
    b = MobileNetV2(scale=0.25, num_classes=7, data_format="NHWC")
    b.set_state_dict(a.state_dict())
    a.eval(), b.eval()
    x = np.random.RandomState(0).randn(2, 3, 32, 32).astype("float32")
    ya = a(paddle.to_tensor(x)).numpy()
    yb = b(paddle.to_tensor(
        np.ascontiguousarray(x.transpose(0, 2, 3, 1)))).numpy()
    np.testing.assert_allclose(ya, yb, rtol=2e-4, atol=2e-4)
