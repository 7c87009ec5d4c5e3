"""Detection machinery: anchors, fixed-K NMS, the Proposal op, and the
Proposal -> ROIPooling pipeline (the rcnn analog; reference
``example/rcnn/rcnn/symbol.py``'s proposal path redesigned static-shape
for XLA — see ops/detection_ops.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu.ops.detection_ops import (bbox_transform_inv, fixed_nms,
                                         generate_anchors)


def test_generate_anchors_centers_and_areas():
    a = generate_anchors(8, scales=(2.0,), ratios=(1.0,), height=4, width=4)
    assert a.shape == (16, 4)
    # first anchor centered at (4, 4) with side 16
    cx = (a[0, 0] + a[0, 2]) / 2
    cy = (a[0, 1] + a[0, 3]) / 2
    assert (cx, cy) == (4.0, 4.0)
    np.testing.assert_allclose(a[0, 2] - a[0, 0], 16.0)
    # stride spacing
    cx2 = (a[1, 0] + a[1, 2]) / 2
    assert cx2 - cx == 8.0


def test_bbox_transform_inv_zero_deltas_identity():
    anchors = jnp.asarray([[0.0, 0, 10, 10], [5, 5, 20, 30]])
    out = bbox_transform_inv(anchors, jnp.zeros((2, 4)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(anchors),
                               rtol=1e-5, atol=1e-4)


def test_fixed_nms_suppresses_overlaps():
    boxes = jnp.asarray([
        [0.0, 0, 10, 10],     # score .9
        [1.0, 1, 11, 11],     # overlaps #0 heavily -> suppressed
        [50.0, 50, 60, 60],   # score .8, disjoint -> kept
        [51.0, 51, 61, 61],   # overlaps #2 -> suppressed
    ])
    scores = jnp.asarray([0.9, 0.85, 0.8, 0.75])
    out_boxes, out_scores = fixed_nms(boxes, scores, k=3,
                                      iou_threshold=0.5)
    ob = np.asarray(out_boxes)
    os_ = np.asarray(out_scores)
    np.testing.assert_allclose(ob[0], [0, 0, 10, 10])
    np.testing.assert_allclose(ob[1], [50, 50, 60, 60])
    assert os_[2] == -np.inf            # only 2 survivors; slot 3 empty
    np.testing.assert_allclose(ob[2], 0)


def test_proposal_symbol_shapes_and_decode():
    b, a, h, w = 2, 1, 8, 8
    k = 4
    net = sym.Proposal(cls_prob=sym.Variable("cls"),
                       bbox_pred=sym.Variable("bbox"),
                       im_info=sym.Variable("info"),
                       feature_stride=8, scales=(2.0,), ratios=(1.0,),
                       rpn_pre_nms_top_n=32, rpn_post_nms_top_n=k,
                       threshold=0.7, rpn_min_size=2, name="prop")
    ex = net.simple_bind(ctx=mx.cpu(), cls=(b, 2 * a, h, w),
                         bbox=(b, 4 * a, h, w), info=(b, 3))
    rng = np.random.RandomState(0)
    cls = np.zeros((b, 2 * a, h, w), np.float32)
    cls[:, a:] = rng.rand(b, a, h, w)  # fg scores
    # make one location the clear winner in image 0
    cls[0, a, 3, 5] = 10.0
    ex.arg_dict["cls"][:] = cls
    ex.arg_dict["bbox"][:] = np.zeros((b, 4 * a, h, w), np.float32)
    ex.arg_dict["info"][:] = np.asarray([[64, 64, 1]] * b, np.float32)
    ex.forward(is_train=False)
    rois = ex.outputs[0].asnumpy()
    assert rois.shape == (b * k, 5)
    # batch indices: first k rows image 0, next k image 1
    np.testing.assert_allclose(rois[:k, 0], 0)
    np.testing.assert_allclose(rois[k:, 0], 1)
    # top roi of image 0 = the winning anchor (zero deltas -> anchor box,
    # centered at stride*(x+0.5) = (44, 28), side 16, clipped to image)
    top = rois[0, 1:]
    np.testing.assert_allclose(top, [36, 20, 52, 36], atol=1.0)


def test_proposal_feeds_roi_pooling():
    """The full symbol pipeline: features + RPN outputs -> Proposal ->
    ROIPooling; shapes stay static end to end."""
    b, a, h, w = 1, 1, 8, 8
    k = 3
    feat = sym.Variable("feat")
    rois = sym.Proposal(cls_prob=sym.Variable("cls"),
                        bbox_pred=sym.Variable("bbox"),
                        im_info=sym.Variable("info"),
                        feature_stride=8, scales=(2.0,), ratios=(1.0,),
                        rpn_pre_nms_top_n=16, rpn_post_nms_top_n=k,
                        rpn_min_size=2, name="prop")
    pooled = sym.ROIPooling(data=feat, rois=rois, pooled_size=(2, 2),
                            spatial_scale=1.0 / 8, name="pool")
    ex = pooled.simple_bind(ctx=mx.cpu(), feat=(b, 6, h, w),
                            cls=(b, 2 * a, h, w), bbox=(b, 4 * a, h, w),
                            info=(b, 3))
    rng = np.random.RandomState(1)
    ex.arg_dict["feat"][:] = rng.rand(b, 6, h, w)
    cls = np.zeros((b, 2 * a, h, w), np.float32)
    cls[:, a:] = rng.rand(b, a, h, w)
    ex.arg_dict["cls"][:] = cls
    ex.arg_dict["bbox"][:] = 0
    ex.arg_dict["info"][:] = np.asarray([[64, 64, 1]], np.float32)
    ex.forward(is_train=False)
    out = ex.outputs[0].asnumpy()
    assert out.shape == (b * k, 6, 2, 2)
    assert np.all(np.isfinite(out))


def test_rcnn_example_end_to_end():
    """The full rcnn-style pipeline trains: RPN objectness converges,
    proposal recall@0.5 reaches a useful level, ROI head trains on
    host-assigned proposal labels (the proposal_target analog)."""
    import importlib.util
    import os
    import sys
    spec = importlib.util.spec_from_file_location(
        "rcnn_example", os.path.join(os.path.dirname(__file__), "..",
                                     "examples", "rcnn_detection.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    old_argv = sys.argv
    sys.argv = ["rcnn_detection.py", "--steps", "120"]
    # the example draws its initial weights from the package's global
    # key: start it where a fresh process does, whatever ran before
    mx.random.seed(0)
    try:
        recalls, accs = mod.main()
    finally:
        sys.argv = old_argv
    assert recalls[-1] >= 0.5, recalls
    assert accs[-1] >= 0.5, accs
