"""Contrib ops (reference: src/operator/contrib/**).

Subset covering the reference's model configs: transformer helpers
(transformer.cc:34 div_sqrt_dim), detection ops for SSD (multibox_prior/
target/detection multibox_*.cc, box_nms bounding_box.cc), roi_align
(roi_align.cc), resize ops (bilinear_resize-inl.h, adaptive_avg_pooling.cc),
fft (fft-inl.h), the `quadratic` tutorial op (quadratic_op-inl.h), boolean
mask and index ops. Dynamic-output-shape ops (box_nms, boolean_mask) keep
static shapes by returning masked/padded results with -1 sentinels, the
standard TPU formulation (SURVEY §7.8(b))."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import register


@register("_contrib_div_sqrt_dim", aliases=("div_sqrt_dim",))
def div_sqrt_dim(data):
    """reference: src/operator/contrib/transformer.cc:34 — scale by 1/sqrt(d)."""
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


@register("_contrib_quadratic", aliases=("quadratic",))
def quadratic(data, a=0.0, b=0.0, c=0.0):
    """reference: src/operator/contrib/quadratic_op-inl.h (the tutorial op)."""
    return a * jnp.square(data) + b * data + c


@register("_contrib_arange_like", aliases=("arange_like",))
def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    if axis is None:
        n = data.size
        out = start + step * jnp.arange(n, dtype=data.dtype)
        return out.reshape(data.shape)
    n = data.shape[axis]
    return start + step * jnp.arange(n, dtype=data.dtype)


@register("_contrib_BilinearResize2D", aliases=("BilinearResize2D", "bilinear_resize_2d"))
def bilinear_resize_2d(data, height=1, width=1, scale_height=None, scale_width=None,
                       mode="size", align_corners=True):
    """reference: bilinear_resize-inl.h — the default resize maps corners
    to corners (align_corners=True, src = dst*(in-1)/(out-1)); with
    align_corners=False it is the half-pixel convention, which is what
    jax.image.resize implements."""
    n, c, h, w = data.shape
    if scale_height is not None:
        height = int(h * scale_height)
        width = int(w * (scale_width if scale_width is not None
                         else scale_height))
    if not align_corners:
        return jax.image.resize(data, (n, c, height, width), method="bilinear")

    def axis_coords(in_sz, out_sz):
        if out_sz == 1:
            return jnp.zeros((1,))
        return jnp.linspace(0.0, in_sz - 1.0, out_sz)

    ys = axis_coords(h, height)
    xs = axis_coords(w, width)
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    wy = (ys - y0).astype(data.dtype).reshape((1, 1, height, 1))
    x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wx = (xs - x0).astype(data.dtype).reshape((1, 1, 1, width))
    rows0 = jnp.take(data, y0, axis=2)
    rows1 = jnp.take(data, y1, axis=2)
    rowi = rows0 * (1 - wy) + rows1 * wy          # (n, c, height, w)
    c0 = jnp.take(rowi, x0, axis=3)
    c1 = jnp.take(rowi, x1, axis=3)
    return c0 * (1 - wx) + c1 * wx


@register("_contrib_AdaptiveAvgPooling2D", aliases=("AdaptiveAvgPooling2D",))
def adaptive_avg_pooling_2d(data, output_size=()):
    n, c, h, w = data.shape
    if not output_size:
        oh = ow = 1
    elif isinstance(output_size, int):
        oh = ow = output_size
    else:
        oh, ow = output_size if len(output_size) == 2 else (output_size[0],) * 2
    if h % oh == 0 and w % ow == 0:
        x = data.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    return jax.image.resize(data, (n, c, oh, ow), method="linear")


@register("_contrib_boolean_mask", aliases=("boolean_mask",), num_outputs=1)
def boolean_mask(data, index, axis=0):
    """Static-shape variant: invalid rows are zeroed and compacted to the
    front; the true count is data-dependent so TPU keeps the full size
    (reference returns a dynamically-sized array, contrib/boolean_mask.cc)."""
    mask = index.astype(bool)
    order = jnp.argsort(~mask, stable=True)
    gathered = jnp.take(data, order, axis=axis)
    keep = jnp.sort(mask)[::-1]
    bshape = (-1,) + (1,) * (data.ndim - 1 - axis)
    return gathered * keep.reshape(bshape).astype(data.dtype)


@register("_contrib_index_copy", aliases=("index_copy",))
def index_copy(old, index, new):
    return old.at[index.astype(jnp.int32)].set(new)


@register("_contrib_index_array", aliases=("index_array",))
def index_array(data, axes=None):
    """Index coordinates of every element: shape data.shape + (len(axes),)
    (reference: src/operator/contrib/index_array.cc — the full data shape is
    kept even when only a subset of axes is requested)."""
    axes = tuple(axes) if axes else tuple(range(data.ndim))
    comps = []
    for a in axes:
        shape1 = [1] * data.ndim
        shape1[a] = data.shape[a]
        comps.append(jnp.broadcast_to(
            jnp.arange(data.shape[a]).reshape(shape1), data.shape))
    # int32 (int64 policy): avoids the per-call x64 truncation warning
    return jnp.stack(comps, axis=-1).astype(jnp.int32)


@register("_contrib_fft", aliases=("fft",))
def fft(data, compute_size=128):
    out = jnp.fft.fft(data.astype(jnp.complex64), axis=-1)
    return jnp.stack([out.real, out.imag], axis=-1).reshape(data.shape[:-1] + (2 * data.shape[-1],))


@register("_contrib_ifft", aliases=("ifft",))
def ifft(data, compute_size=128):
    n = data.shape[-1] // 2
    cplx = data.reshape(data.shape[:-1] + (n, 2))
    out = jnp.fft.ifft(cplx[..., 0] + 1j * cplx[..., 1], axis=-1)
    return out.real.astype(jnp.float32) * n


# --------------------------------------------------------------------------
# ROI ops (reference: roi_align.cc, ../roi_pooling.cc)
# --------------------------------------------------------------------------

def _bilinear_sample(feat, y, x):
    """feat: (C,H,W); y,x scalars (traced)."""
    h, w = feat.shape[1], feat.shape[2]
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    wy = y - y0
    wx = x - x0

    def g(yy, xx):
        yi = jnp.clip(yy.astype(jnp.int32), 0, h - 1)
        xi = jnp.clip(xx.astype(jnp.int32), 0, w - 1)
        return feat[:, yi, xi]

    return (g(y0, x0) * (1 - wy) * (1 - wx) + g(y0, x0 + 1) * (1 - wy) * wx
            + g(y0 + 1, x0) * wy * (1 - wx) + g(y0 + 1, x0 + 1) * wy * wx)


@register("_contrib_ROIAlign", aliases=("ROIAlign", "roi_align"))
def roi_align(data, rois, pooled_size=(7, 7), spatial_scale=1.0, sample_ratio=2,
              position_sensitive=False, aligned=False):
    ph, pw = pooled_size
    sr = max(int(sample_ratio), 1)
    offset = 0.5 if aligned else 0.0

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = roi[1] * spatial_scale - offset, roi[2] * spatial_scale - offset, \
            roi[3] * spatial_scale - offset, roi[4] * spatial_scale - offset
        rh = jnp.maximum(y2 - y1, 1.0)
        rw = jnp.maximum(x2 - x1, 1.0)
        bh, bw = rh / ph, rw / pw
        feat = data[jnp.clip(bidx, 0, data.shape[0] - 1)]

        iy = (jnp.arange(ph)[:, None, None, None] * bh + y1
              + (jnp.arange(sr)[None, None, :, None] + 0.5) * bh / sr)
        ix = (jnp.arange(pw)[None, :, None, None] * bw + x1
              + (jnp.arange(sr)[None, None, None, :] + 0.5) * bw / sr)
        ys = jnp.broadcast_to(iy, (ph, pw, sr, sr)).reshape(-1)
        xs = jnp.broadcast_to(ix, (ph, pw, sr, sr)).reshape(-1)
        samples = jax.vmap(lambda y, x: _bilinear_sample(feat, y, x))(ys, xs)
        samples = samples.reshape(ph, pw, sr * sr, -1).mean(axis=2)
        return jnp.moveaxis(samples, -1, 0)  # (C, ph, pw)

    return jax.vmap(one_roi)(rois)


@register("ROIPooling")
def roi_pooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0):
    ph, pw = pooled_size

    def one_roi(roi):
        bidx = jnp.clip(roi[0].astype(jnp.int32), 0, data.shape[0] - 1)
        x1 = jnp.round(roi[1] * spatial_scale)
        y1 = jnp.round(roi[2] * spatial_scale)
        x2 = jnp.round(roi[3] * spatial_scale)
        y2 = jnp.round(roi[4] * spatial_scale)
        rh = jnp.maximum(y2 - y1 + 1, 1.0)
        rw = jnp.maximum(x2 - x1 + 1, 1.0)
        feat = data[bidx]
        h, w = feat.shape[1], feat.shape[2]
        gy = jnp.arange(h, dtype=jnp.float32)
        gx = jnp.arange(w, dtype=jnp.float32)
        biny = jnp.clip(jnp.floor((gy - y1) * ph / rh), -1, ph - 1)
        binx = jnp.clip(jnp.floor((gx - x1) * pw / rw), -1, pw - 1)
        inside_y = (gy >= y1) & (gy <= y2)
        inside_x = (gx >= x1) & (gx <= x2)
        out = jnp.full((feat.shape[0], ph, pw), -jnp.inf, feat.dtype)
        oh = jnp.where(inside_y, biny, ph).astype(jnp.int32)
        ow = jnp.where(inside_x, binx, pw).astype(jnp.int32)
        padded = jnp.full((feat.shape[0], ph + 1, pw + 1), -jnp.inf, feat.dtype)
        padded = padded.at[:, oh[:, None], ow[None, :]].max(feat)
        out = padded[:, :ph, :pw]
        return jnp.where(jnp.isfinite(out), out, 0.0)

    return jax.vmap(one_roi)(rois)


# --------------------------------------------------------------------------
# SSD / detection ops (reference: multibox_prior.cc, multibox_target.cc,
# multibox_detection.cc, bounding_box.cc)
# --------------------------------------------------------------------------

@register("_contrib_MultiBoxPrior", aliases=("MultiBoxPrior",))
def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False, steps=(-1.0, -1.0),
                   offsets=(0.5, 0.5)):
    import numpy as np

    h, w = data.shape[2], data.shape[3]
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w
    cy = (np.arange(h) + offsets[0]) * step_y
    cx = (np.arange(w) + offsets[1]) * step_x
    cy, cx = np.meshgrid(cy, cx, indexing="ij")
    boxes = []
    num = len(sizes) + len(ratios) - 1
    for i in range(num):
        if i < len(sizes):
            s = sizes[i]
            bw = bh = s / 2.0
            bw *= np.sqrt(ratios[0])
            bh /= np.sqrt(ratios[0])
        else:
            r = ratios[i - len(sizes) + 1]
            bw = sizes[0] / 2.0 * np.sqrt(r)
            bh = sizes[0] / 2.0 / np.sqrt(r)
        boxes.append(np.stack([cx - bw, cy - bh, cx + bw, cy + bh], axis=-1))
    out = np.stack(boxes, axis=2).reshape(1, -1, 4).astype(np.float32)
    if clip:
        out = np.clip(out, 0.0, 1.0)
    return jnp.asarray(out)


def _box_iou_corner(a, b):
    """a: (..., 4), b: (..., 4) corner format; broadcast IoU."""
    tl = jnp.maximum(a[..., :2], b[..., :2])
    br = jnp.minimum(a[..., 2:], b[..., 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum((a[..., 2] - a[..., 0]) * (a[..., 3] - a[..., 1]), 0.0)
    area_b = jnp.maximum((b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1]), 0.0)
    return inter / jnp.maximum(area_a + area_b - inter, 1e-12)


@register("_contrib_box_iou", aliases=("box_iou",))
def box_iou(lhs, rhs, format="corner"):
    return _box_iou_corner(lhs[..., :, None, :], rhs[..., None, :, :])


@register("_contrib_MultiBoxTarget", aliases=("MultiBoxTarget",), num_outputs=3)
def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """Anchor matching + target encoding for SSD training
    (reference: src/operator/contrib/multibox_target.cc)."""
    anchors = anchor.reshape(-1, 4)  # (A,4)
    A = anchors.shape[0]

    def per_sample(lab, cls_p):
        # lab: (M, 5+) [cls, x1, y1, x2, y2]; cls_p: (C, A) raw predictions
        valid = lab[:, 0] >= 0
        ious = _box_iou_corner(anchors[:, None, :], lab[None, :, 1:5])  # (A,M)
        ious = jnp.where(valid[None, :], ious, 0.0)
        best_gt = jnp.argmax(ious, axis=1)
        best_iou = jnp.max(ious, axis=1)
        matched = best_iou > overlap_threshold
        # force-match the best anchor for each gt
        best_anchor = jnp.argmax(ious, axis=0)  # (M,)
        forced = jnp.zeros(A, bool).at[best_anchor].set(valid)
        matched = matched | forced
        gt = lab[best_gt]
        cls_target = jnp.where(matched, gt[:, 0] + 1.0, 0.0)
        if negative_mining_ratio > 0:
            # hard negative mining (reference multibox_target.cc): unmatched
            # anchors below the mining IoU threshold are ranked by their
            # predicted non-background confidence; the hardest ratio*num_pos
            # stay background, the rest get ignore_label. Static-shape: the
            # dynamic quota is a rank comparison, not a gather.
            prob = jax.nn.softmax(cls_p, axis=0)           # (C, A)
            hardness = 1.0 - prob[0]                        # non-bg confidence
            candidate = (~matched) & (best_iou < negative_mining_thresh)
            score = jnp.where(candidate, hardness, -jnp.inf)
            order = jnp.argsort(-score)
            rank = jnp.zeros(A, jnp.int32).at[order].set(jnp.arange(A, dtype=jnp.int32))
            quota = jnp.maximum(
                (negative_mining_ratio * jnp.sum(matched)).astype(jnp.int32),
                jnp.int32(minimum_negative_samples))
            keep_neg = candidate & (rank < quota)
            cls_target = jnp.where(matched, cls_target,
                                   jnp.where(keep_neg, 0.0, float(ignore_label)))
        # encode regression targets (center form, variances)
        aw = anchors[:, 2] - anchors[:, 0]
        ah = anchors[:, 3] - anchors[:, 1]
        acx = (anchors[:, 0] + anchors[:, 2]) / 2
        acy = (anchors[:, 1] + anchors[:, 3]) / 2
        gw = jnp.maximum(gt[:, 3] - gt[:, 1], 1e-12)
        gh = jnp.maximum(gt[:, 4] - gt[:, 2], 1e-12)
        gcx = (gt[:, 1] + gt[:, 3]) / 2
        gcy = (gt[:, 2] + gt[:, 4]) / 2
        tx = (gcx - acx) / jnp.maximum(aw, 1e-12) / variances[0]
        ty = (gcy - acy) / jnp.maximum(ah, 1e-12) / variances[1]
        tw = jnp.log(gw / jnp.maximum(aw, 1e-12)) / variances[2]
        th = jnp.log(gh / jnp.maximum(ah, 1e-12)) / variances[3]
        loc_t = jnp.stack([tx, ty, tw, th], axis=-1)
        loc_t = jnp.where(matched[:, None], loc_t, 0.0)
        loc_mask = jnp.where(matched[:, None], 1.0, 0.0)
        loc_mask = jnp.broadcast_to(loc_mask, (A, 4))
        return loc_t.reshape(-1), loc_mask.reshape(-1), cls_target

    # targets are training labels, not differentiable functions of the
    # predictions (reference MultiBoxTarget registers no gradient)
    loc_target, loc_mask, cls_target = jax.vmap(per_sample)(
        lax.stop_gradient(label), lax.stop_gradient(cls_pred))
    return loc_target, loc_mask, cls_target


@register("_contrib_MultiBoxDetection", aliases=("MultiBoxDetection",))
def multibox_detection(cls_prob, loc_pred, anchor, clip=True, threshold=0.01,
                       background_id=0, nms_threshold=0.5, force_suppress=False,
                       variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """Decode + per-class NMS, static shapes (invalid -> id=-1).
    reference: src/operator/contrib/multibox_detection.cc"""
    anchors = anchor.reshape(-1, 4)
    A = anchors.shape[0]
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    acx = (anchors[:, 0] + anchors[:, 2]) / 2
    acy = (anchors[:, 1] + anchors[:, 3]) / 2

    def per_sample(cls_p, loc_p):
        # cls_p: (C, A); loc_p: (A*4,)
        loc = loc_p.reshape(A, 4)
        cx = loc[:, 0] * variances[0] * aw + acx
        cy = loc[:, 1] * variances[1] * ah + acy
        w = jnp.exp(loc[:, 2] * variances[2]) * aw / 2
        h = jnp.exp(loc[:, 3] * variances[3]) * ah / 2
        boxes = jnp.stack([cx - w, cy - h, cx + w, cy + h], axis=-1)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        scores = cls_p[1:] if background_id == 0 else cls_p  # (C-1, A)
        cls_id = jnp.argmax(scores, axis=0).astype(jnp.float32)
        score = jnp.max(scores, axis=0)
        keep_score = score > threshold
        # greedy NMS over all anchors (class-aware unless force_suppress)
        order = jnp.argsort(-score)
        boxes_o = boxes[order]
        ids_o = cls_id[order]
        score_o = score[order]
        keep_o = keep_score[order]
        ious = _box_iou_corner(boxes_o[:, None, :], boxes_o[None, :, :])
        same = jnp.ones((A, A), bool) if force_suppress else (ids_o[:, None] == ids_o[None, :])
        sup_mat = (ious > nms_threshold) & same

        def body(i, alive):
            cur = alive[i]
            kill = sup_mat[i] & (jnp.arange(A) > i) & cur
            return alive & ~kill

        alive = lax.fori_loop(0, A, body, keep_o)
        out_id = jnp.where(alive & keep_o, ids_o, -1.0)
        return jnp.concatenate([out_id[:, None], score_o[:, None], boxes_o], axis=-1)

    return jax.vmap(per_sample)(cls_prob, loc_pred)


@register("_contrib_box_nms", aliases=("box_nms",))
def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1, coord_start=2,
            score_index=1, id_index=-1, background_id=-1, force_suppress=False,
            in_format="corner", out_format="corner"):
    """Static-shape NMS: suppressed entries get score column set to -1
    (reference: src/operator/contrib/bounding_box.cc)."""
    shape = data.shape
    flat = data.reshape((-1,) + shape[-2:])

    def per_batch(d):
        n = d.shape[0]
        score = d[:, score_index]
        boxes = lax.dynamic_slice_in_dim(d, coord_start, 4, axis=1)
        if in_format == "center":
            cx, cy, w, h = boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]
            boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], -1)
        valid = score > valid_thresh
        order = jnp.argsort(-score)
        d_o = d[order]
        b_o = boxes[order]
        v_o = valid[order]
        if id_index >= 0 and not force_suppress:
            ids = d_o[:, id_index]
            same = ids[:, None] == ids[None, :]
        else:
            same = jnp.ones((n, n), bool)
        ious = _box_iou_corner(b_o[:, None, :], b_o[None, :, :])
        sup = (ious > overlap_thresh) & same

        def body(i, alive):
            cur = alive[i]
            kill = sup[i] & (jnp.arange(n) > i) & cur
            return alive & ~kill

        alive = lax.fori_loop(0, n, body, v_o)
        out = d_o.at[:, score_index].set(jnp.where(alive, d_o[:, score_index], -1.0))
        return out

    out = jax.vmap(per_batch)(flat)
    return out.reshape(shape)


@register("_contrib_count_sketch", aliases=("count_sketch",))
def count_sketch(data, h, s, out_dim=0, processing_batch_size=32):
    idx = h.astype(jnp.int32).reshape(-1)
    sign = s.reshape(-1)
    out = jnp.zeros(data.shape[:-1] + (out_dim,), data.dtype)
    return out.at[..., idx].add(data * sign)


@register("SVMOutput")
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0, use_linear=False):
    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        depth = d.shape[-1]
        onehot = jax.nn.one_hot(l.astype(jnp.int32), depth, dtype=d.dtype)
        score_gt = jnp.sum(d * onehot, axis=-1, keepdims=True)
        if use_linear:
            viol = ((margin - (score_gt - d)) > 0).astype(d.dtype) * (1 - onehot)
            grad = viol - onehot * jnp.sum(viol, axis=-1, keepdims=True)
        else:
            m = jnp.maximum(margin - (score_gt - d), 0.0) * (1 - onehot)
            grad = 2 * m - 2 * onehot * jnp.sum(m, axis=-1, keepdims=True)
        return grad * regularization_coefficient, jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _contrib_flash_attention(q, k, v, causal=False, sm_scale=None):
    """Pallas flash attention over (..., L, D) inputs (ops/pallas_kernels.py;
    the TPU replacement for batch_dot+softmax+batch_dot attention assembled
    from reference primitives, src/operator/contrib/transformer.cc)."""
    from . import pallas_kernels

    return pallas_kernels.flash_attention(q, k, v, causal=causal,
                                          sm_scale=sm_scale)


# --------------------------------------------------------------------------
# RPN Proposal (reference: src/operator/contrib/proposal-inl.h:93 — anchors
# + bbox deltas -> clip -> min-size filter -> top-k -> NMS -> fixed-count
# rois). Static shapes throughout: top-k and the NMS alive-mask keep XLA
# happy; short outputs pad by repeating the best proposal like the
# reference's workspace fill.
# --------------------------------------------------------------------------

def _rpn_anchors(h, w, stride, scales, ratios):
    import numpy as np

    base = float(stride)
    anchors = []
    for r in ratios:
        for s in scales:
            size = base * base * s * s
            w_a = np.sqrt(size / r)
            h_a = w_a * r
            anchors.append([-(w_a - 1) / 2, -(h_a - 1) / 2,
                            (w_a - 1) / 2, (h_a - 1) / 2])
    base_a = np.asarray(anchors, np.float32)          # (A, 4)
    cy, cx = np.meshgrid(np.arange(h) * stride, np.arange(w) * stride,
                         indexing="ij")
    shift = np.stack([cx, cy, cx, cy], axis=-1).reshape(-1, 1, 4)
    return (shift + base_a[None]).reshape(-1, 4)      # (H*W*A, 4)


@register("_contrib_Proposal", num_outputs=-1,
          num_outputs_fn=lambda attrs: 2 if attrs.get("output_score") else 1,
          aliases=("Proposal", "_contrib_MultiProposal", "MultiProposal"))
def proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16,
             output_score=False, iou_loss=False):
    """cls_prob (B, 2A, H, W), bbox_pred (B, 4A, H, W), im_info (B, 3)
    -> rois (B*post_nms_top_n, 5) [batch_idx, x1, y1, x2, y2]."""
    b, c2a, h, w = cls_prob.shape
    na = c2a // 2
    anchors = jnp.asarray(_rpn_anchors(h, w, feature_stride, scales, ratios))
    total = anchors.shape[0]
    pre_n = min(int(rpn_pre_nms_top_n), total)
    post_n = int(rpn_post_nms_top_n)

    def per_image(scores, deltas, info):
        # scores (2A, H, W) -> fg (A, H, W) -> (H*W*A,)
        fg = scores[na:].transpose(1, 2, 0).reshape(-1)
        d = deltas.reshape(na, 4, h, w).transpose(2, 3, 0, 1).reshape(-1, 4)
        ah = anchors[:, 3] - anchors[:, 1] + 1.0
        aw = anchors[:, 2] - anchors[:, 0] + 1.0
        acx = anchors[:, 0] + 0.5 * (aw - 1)
        acy = anchors[:, 1] + 0.5 * (ah - 1)
        cx = d[:, 0] * aw + acx
        cy = d[:, 1] * ah + acy
        pw = jnp.exp(jnp.clip(d[:, 2], -10, 10)) * aw
        ph = jnp.exp(jnp.clip(d[:, 3], -10, 10)) * ah
        x1 = cx - 0.5 * (pw - 1)
        y1 = cy - 0.5 * (ph - 1)
        x2 = cx + 0.5 * (pw - 1)
        y2 = cy + 0.5 * (ph - 1)
        # clip to image (im_info = [height, width, scale])
        x1 = jnp.clip(x1, 0, info[1] - 1.0)
        y1 = jnp.clip(y1, 0, info[0] - 1.0)
        x2 = jnp.clip(x2, 0, info[1] - 1.0)
        y2 = jnp.clip(y2, 0, info[0] - 1.0)
        boxes = jnp.stack([x1, y1, x2, y2], axis=-1)
        min_size = rpn_min_size * info[2]
        keep = ((x2 - x1 + 1.0) >= min_size) & ((y2 - y1 + 1.0) >= min_size)
        score = jnp.where(keep, fg, -jnp.inf)
        top_s, top_i = jax.lax.top_k(score, pre_n)
        top_b = boxes[top_i]

        def body(i, alive):
            # one IoU row per step: keeps NMS memory O(pre_n) instead of a
            # pre_n^2 matrix (6000^2 f32 = 144MB/image at the default top_n)
            row = _box_iou_corner(top_b[i][None, :], top_b)
            cur = alive[i]
            kill = (row > threshold) & (jnp.arange(pre_n) > i) & cur
            return alive & ~kill

        alive = lax.fori_loop(0, pre_n, body,
                              jnp.isfinite(top_s))
        # order survivors first (stable), pad by repeating the best
        rank = jnp.argsort(~alive, stable=True)
        sel = rank[:post_n] if post_n <= pre_n else \
            jnp.concatenate([rank, jnp.zeros(post_n - pre_n, rank.dtype)])
        out_boxes = top_b[sel]
        out_alive = alive[sel]
        out_boxes = jnp.where(out_alive[:, None], out_boxes, top_b[0])
        out_score = jnp.where(out_alive, top_s[sel], top_s[0])
        return out_boxes, out_score

    boxes, scores = jax.vmap(per_image)(cls_prob, bbox_pred, im_info)
    batch_ids = jnp.repeat(jnp.arange(b, dtype=boxes.dtype), post_n)
    rois = jnp.concatenate([batch_ids[:, None],
                            boxes.reshape(-1, 4)], axis=-1)
    if output_score:
        return rois, scores.reshape(-1, 1)
    return rois


# --------------------------------------------------------------------------
# DeformableConvolution (reference:
# src/operator/contrib/deformable_convolution-inl.h:99 — bilinear sampling
# at learned per-tap offsets, then a standard grouped conv contraction).
# TPU-native: the sampled column tensor is built with vectorized gathers
# (XLA fuses the 4-corner interpolation) and contracted with one einsum on
# the MXU — no explicit im2col buffer in HBM.
# --------------------------------------------------------------------------

@register("_contrib_DeformableConvolution", aliases=("DeformableConvolution",))
def deformable_convolution(data, offset, weight, bias=None, kernel=(),
                           stride=(), dilate=(), pad=(), num_filter=0,
                           num_group=1, num_deformable_group=1,
                           no_bias=False, workspace=0, layout=None):
    n, c, h, w = data.shape
    kh, kw = kernel
    sh, sw = stride if stride else (1, 1)
    dh, dw = dilate if dilate else (1, 1)
    ph, pw = pad if pad else (0, 0)
    g = int(num_group)
    dg = int(num_deformable_group)
    oh = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    ow = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1

    oy = jnp.arange(oh) * sh - ph
    ox = jnp.arange(ow) * sw - pw
    ky = jnp.arange(kh) * dh
    kx = jnp.arange(kw) * dw
    # base sampling grids (kh, kw, oh, ow)
    base_y = jnp.broadcast_to(
        (oy[None, None, :, None] + ky[:, None, None, None]).astype(data.dtype),
        (kh, kw, oh, ow))
    base_x = jnp.broadcast_to(
        (ox[None, None, None, :] + kx[None, :, None, None]).astype(data.dtype),
        (kh, kw, oh, ow))

    # reference offset layout: (N, dg*2*kh*kw, OH, OW), y before x per tap
    off = offset.reshape(n, dg, kh * kw, 2, oh, ow) \
                .reshape(n, dg, kh, kw, 2, oh, ow)
    sy = base_y[None, None] + off[:, :, :, :, 0]
    sx = base_x[None, None] + off[:, :, :, :, 1]   # (N, dg, kh, kw, oh, ow)

    def bilinear(img, y, x):
        # img (C', H, W); y/x (kh, kw, oh, ow)
        y0 = jnp.floor(y)
        x0 = jnp.floor(x)
        wy = y - y0
        wx = x - x0

        def at(yy, xx):
            inb = (yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1)
            yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
            xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
            v = img[:, yc, xc]                      # (C', kh, kw, oh, ow)
            return jnp.where(inb[None], v, 0.0)

        return (at(y0, x0) * ((1 - wy) * (1 - wx))[None]
                + at(y0, x0 + 1) * ((1 - wy) * wx)[None]
                + at(y0 + 1, x0) * (wy * (1 - wx))[None]
                + at(y0 + 1, x0 + 1) * (wy * wx)[None])

    def per_sample(img, y, x):
        # img (C, H, W) split into dg channel groups sharing offsets
        imgs = img.reshape(dg, c // dg, h, w)
        cols = jax.vmap(bilinear)(imgs, y, x)       # (dg, C/dg, kh, kw, ...)
        return cols.reshape(c, kh, kw, oh, ow)

    cols = jax.vmap(per_sample)(data, sy, sx)       # (N, C, kh, kw, oh, ow)
    cols = cols.reshape(n, g, c // g, kh, kw, oh, ow)
    wgt = weight.reshape(g, num_filter // g, c // g, kh, kw)
    out = jnp.einsum("ngcijyx,gocij->ngoyx", cols, wgt,
                     preferred_element_type=jnp.float32)
    out = out.reshape(n, num_filter, oh, ow).astype(data.dtype)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


# --------------------------------------------------------------------------
# Switch-style mixture-of-experts FFN (NOT in the reference — the expert-
# parallel extension SURVEY §2.3 lists as a TPU-native goal). Top-1 routing
# with capacity, dense dispatch/combine einsums (the GSPMD formulation:
# under a mesh with an `ep` axis the expert tables shard over `ep` and XLA
# lowers the token->expert resharding to an all_to_all over ICI).
# --------------------------------------------------------------------------

@register("_contrib_switch_moe", num_outputs=2, num_visible_outputs=2,
          aliases=("switch_moe",))
def switch_moe(data, gate_weight, expert_w_in, expert_w_out,
               capacity_factor=1.25):
    """data (..., d); gate_weight (E, d); expert tables (E, d, h)/(E, h, d).
    Returns (output (..., d), aux_loss ()) — aux is the Switch load-balance
    loss E * sum_e(frac_tokens_e * frac_probs_e). Exactly `topk_moe` at
    k=1 with unnormalized gates (one shared dispatch body; the router
    z-loss output is dropped — XLA dead-code-eliminates it under jit)."""
    out, lb, _z = topk_moe(data, gate_weight, expert_w_in, expert_w_out,
                           k=1, capacity_factor=capacity_factor,
                           normalize_gates=False)
    return out, lb


@register("_contrib_topk_moe", num_outputs=3, num_visible_outputs=3,
          aliases=("topk_moe",))
def topk_moe(data, gate_weight, expert_w_in, expert_w_out, k=2,
             capacity_factor=1.25, normalize_gates=True):
    """Top-k MoE routing (GShard/Mixtral-style generalization of
    `switch_moe`; k=1 reproduces Switch). data (..., d); gate_weight (E, d);
    expert tables (E, d, h)/(E, h, d). Returns

      (output (..., d), lb_loss (), z_loss ())

    - lb_loss: load-balance loss E * sum_e(frac_tokens_e * frac_probs_e),
      with frac_tokens counting all k assignments (each token contributes
      1/k per choice so a balanced router scores 1.0, as at k=1).
    - z_loss: router z-loss mean_t(logsumexp(logits_t)^2) (ST-MoE) — keeps
      router logits small; scale with your own coefficient (~1e-3).

    Capacity is `capacity_factor * k * T / E` slots per expert, shared
    across choices in priority order (choice 0 claims slots before choice 1,
    matching the GShard dispatch priority); overflow tokens drop that
    choice. The dispatch/combine einsums are the GSPMD formulation: with an
    `ep` mesh axis the (E, C, d) activations shard over `ep` and XLA lowers
    the resharding to ICI all_to_alls, exactly as in `switch_moe`."""
    k = int(k)
    if k < 1:
        raise ValueError("topk_moe: k must be >= 1")
    lead = data.shape[:-1]
    d = data.shape[-1]
    tokens = data.reshape(-1, d)
    t = tokens.shape[0]
    e = gate_weight.shape[0]
    if k > e:
        raise ValueError("topk_moe: k=%d > num_experts=%d" % (k, e))
    cap = max(1, int(capacity_factor * k * t / e))

    logits = jnp.einsum("td,ed->te", tokens, gate_weight,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, k)           # (T, k)
    if normalize_gates and k > 1:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    # Per-choice dispatch with capacity shared across choices: choice j's
    # queue positions start after every earlier choice's claims (k is a
    # small static int, so this Python loop unrolls under trace).
    counts = jnp.zeros((e,), jnp.float32)
    combine = jnp.zeros((t, e, cap), jnp.float32)
    dispatch = jnp.zeros((t, e, cap), jnp.float32)
    onehot_sum = jnp.zeros((t, e), jnp.float32)
    for j in range(k):
        onehot = jax.nn.one_hot(experts[:, j], e, dtype=jnp.float32)
        onehot_sum = onehot_sum + onehot
        pos = ((jnp.cumsum(onehot, axis=0) - 1.0) + counts[None, :]) * onehot
        keep = (pos < cap) & (onehot > 0)
        slot = jax.nn.one_hot(pos.sum(axis=-1).astype(jnp.int32), cap,
                              dtype=jnp.float32)
        dispatch_j = keep.astype(jnp.float32)[:, :, None] * slot[:, None, :]
        dispatch = dispatch + dispatch_j
        combine = combine + dispatch_j * gate_vals[:, j].astype(
            jnp.float32)[:, None, None]
        counts = counts + onehot.sum(axis=0)

    xe = jnp.einsum("tec,td->ecd", dispatch, tokens,
                    preferred_element_type=jnp.float32).astype(data.dtype)
    he = jax.nn.relu(jnp.einsum("ecd,edh->ech", xe, expert_w_in,
                                preferred_element_type=jnp.float32)
                     .astype(data.dtype))
    ye = jnp.einsum("ech,ehd->ecd", he, expert_w_out,
                    preferred_element_type=jnp.float32).astype(data.dtype)
    # combine stays float32 into the mixed-dtype contraction: gates keep
    # their full softmax precision even for bf16 activations
    out = jnp.einsum("tec,ecd->td", combine, ye,
                     preferred_element_type=jnp.float32).astype(data.dtype)

    frac_tokens = onehot_sum.mean(axis=0) / k
    frac_probs = probs.mean(axis=0)
    lb = (frac_tokens * frac_probs).sum() * e
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return (out.reshape(lead + (d,)), lb.astype(jnp.float32),
            z.astype(jnp.float32))


def moe_tile_rows(pairs, experts_held):
    """Rows of a tile of the grouped expert product: about twice the mean
    pairs an expert, a power of two between 16 (a bfloat16 sublane tile)
    and 128."""
    tm = 16
    while tm < 128 and tm * experts_held < 2 * pairs:
        tm *= 2
    return tm


@register("_contrib_sigmoid_topk_moe", num_outputs=2, num_visible_outputs=2,
          aliases=("sigmoid_topk_moe",))
def sigmoid_topk_moe(data, gate_weight, expert_bias, w1, w3, w2,
                     router_data=None, k=4, expert_offset=0, valid=None,
                     routed_scaling_factor=1.0, norm_topk_prob=True,
                     n_group=1, topk_group=1, gate_eps=1e-6,
                     scores="sigmoid", activation="silu"):
    """Drop-free top-k expert layer: the one routing and tile dispatch of
    every expert model (the name is its first router's).

    data (..., C); gate_weight (E, C) and expert_bias (E,; None: no bias)
    over ALL experts; w1, w3, w2 (E_held, F, C): the experts
    ``expert_offset .. expert_offset + E_held`` that this holder computes.
    The router reads ``router_data`` (..., C) where given (a model whose
    router sees the operator's input rows, not the feed-forward's), else
    ``data``. ``scores`` = ``sigmoid``: s = sigmoid(x Wg) in float32; the k
    experts of a token are the top k of
    s + expert_bias (the bias selects, it does not weigh); gates g = s / (sum
    of the selected s + ``gate_eps``) when ``norm_topk_prob``, times
    ``routed_scaling_factor``. ``scores`` = ``softmax_selected``: the k
    experts are the top k of the raw logits z = x Wg (+ expert_bias) and
    the gates a softmax over the k selected logits (they sum to 1, so
    ``norm_topk_prob`` has nothing to do and is not applied), times
    ``routed_scaling_factor``. An expert computes w2 (act(w1 x) * w3 x),
    ``activation`` ``silu`` or ``relu``. With ``n_group`` > 1 the selection is
    group-limited: the experts lie in ``n_group`` groups of consecutive
    indices, a group scores the sum of its two largest biased scores, the
    ``topk_group`` best groups are kept and the biased scores of the others
    are set to 0 before the top k. Every (token, expert) pair whose expert is
    held is computed: rows are laid out expert by expert in tiles of
    `moe_tile_rows` and go through one grouped product
    (`pallas_kernels.moe_grouped_ffn`); there is no capacity and nobody is
    dropped. Pairs of experts held elsewhere add nothing here: the parts
    that all holders give add up to the whole layer. Rows with ``valid``
    false (a bucket's padding) route nowhere and cost no expert.

    Returns ``(output (..., C), stats (3,) int32)``: pairs computed here,
    experts hit, and the busiest expert's pairs."""
    from .pallas_kernels import moe_grouped_ffn

    lead, c = data.shape[:-1], data.shape[-1]
    x = data.reshape(-1, c)
    n = x.shape[0]
    held = w1.shape[0]
    pairs = n * k
    tm = moe_tile_rows(pairs, held)
    tiles = pairs // tm + held          # covers any routing
    if scores not in ("sigmoid", "softmax_selected"):
        raise ValueError("scores is sigmoid or softmax_selected, not %r"
                         % (scores,))
    softmax = scores == "softmax_selected"
    with jax.named_scope("mxtpu.lm.moe.route"):
        logits = jnp.einsum(
            "nc,ec->ne", x if router_data is None
            else router_data.reshape(-1, c), gate_weight,
            preferred_element_type=jnp.float32)
        s = logits if softmax else jax.nn.sigmoid(logits)
        biased = s if expert_bias is None \
            else s + expert_bias.astype(jnp.float32)
        if n_group > 1:
            per = biased.reshape(n, n_group, -1)
            _, keep = lax.top_k(jnp.sum(lax.top_k(per, 2)[0], axis=-1),
                                topk_group)                       # (n, kept)
            kept = jnp.any(keep[:, :, None] == jnp.arange(n_group), axis=1)
            biased = jnp.where(kept[:, :, None], per, 0.0).reshape(n, -1)
        _, experts = lax.top_k(biased, k)
        gates = jnp.take_along_axis(s, experts, axis=1)           # (n, k)
        if softmax:
            gates = jax.nn.softmax(gates, axis=1)
        elif norm_topk_prob:
            gates = gates / (jnp.sum(gates, axis=1, keepdims=True)
                             + gate_eps)
        gates = gates * routed_scaling_factor

        local = experts - expert_offset
        here = (local >= 0) & (local < held)
        if valid is not None:
            here = here & valid.reshape(-1)[:, None]
        pair_expert = jnp.where(here, local, held).reshape(-1)    # (n*k,)
        # a pair's place: its expert's first tile, then its rank in the
        # expert
        onehot = (pair_expert[:, None] == jnp.arange(held)[None, :]) \
            .astype(jnp.int32)                                    # (P, E)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
        counts = jnp.sum(onehot, axis=0)                          # (E,)
        tile_end = jnp.cumsum(-(-counts // tm))                   # (E,)
        tile_start = tile_end - (-(-counts // tm))
        n_tiles = tile_end[-1]
        start = jnp.sum(onehot * tile_start[None, :], axis=1)
        dest = jnp.where(pair_expert < held, start * tm + rank, tiles * tm)
        t_idx = jnp.minimum(jnp.arange(tiles), jnp.maximum(n_tiles - 1, 0))
        tile_expert = jnp.sum(tile_end[None, :] <= t_idx[:, None], axis=1)
        tile_expert = jnp.minimum(tile_expert, held - 1).astype(jnp.int32)
        row_token = jnp.full((tiles * tm,), n, jnp.int32).at[dest].set(
            jnp.arange(pairs, dtype=jnp.int32) // k, mode="drop")
    with jax.named_scope("mxtpu.lm.moe.experts"):
        xs = jnp.concatenate([x, jnp.zeros((1, c), x.dtype)])[row_token]
        ys = moe_grouped_ffn(xs, tile_expert, n_tiles.reshape(1), w1, w3,
                             w2, tm, activation)
        picked = ys.at[dest].get(mode="fill", fill_value=0.0) \
            .reshape(n, k, c)
        out = jnp.sum(picked * gates[:, :, None], axis=1).astype(data.dtype)
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.max(counts)]).astype(jnp.int32)
    return out.reshape(lead + (c,)), stats
