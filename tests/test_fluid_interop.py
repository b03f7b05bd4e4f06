"""Reference-artifact inference interop (VERDICT r3 item 3).

Builds byte-genuine reference-format model directories — `__model__`
ProgramDesc protobuf (framework.proto:202) + LoDTensor param files
(lod_tensor.cc:244 SerializeToStream layout) — with an INDEPENDENT
hand-rolled encoder, then serves them through inference.create_predictor
and checks the forward against numpy. Covers the book-test model shapes
(fit_a_line: mul+elementwise_add; recognize_digits: conv2d+batch_norm+
pool2d+fc+softmax), both separate-param-files and combined layouts.
"""
import os
import struct

import numpy as np
import pytest

from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.inference.fluid_program import (load_fluid_model,
                                                parse_program_desc,
                                                read_lod_tensor)


# -- independent proto2 wire writer ------------------------------------------

def _varint(v):
    if v < 0:
        v += 1 << 64  # two's complement (proto2 int32/int64 negatives)
    out = b''
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _tag(field, wire):
    return _varint((field << 3) | wire)


def _len_field(field, payload):
    return _tag(field, 2) + _varint(len(payload)) + payload


def _vint_field(field, v):
    return _tag(field, 0) + _varint(v)


def _f32_field(field, v):
    return _tag(field, 5) + struct.pack('<f', v)


def _str_field(field, s):
    return _len_field(field, s.encode())


def _attr(name, atype, value):
    out = _str_field(1, name) + _vint_field(2, atype)
    if atype == 0:      # INT
        out += _vint_field(3, value)
    elif atype == 1:    # FLOAT
        out += _f32_field(4, value)
    elif atype == 2:    # STRING
        out += _str_field(5, value)
    elif atype == 3:    # INTS (proto2 default: unpacked)
        for v in value:
            out += _vint_field(6, v)
    elif atype == 6:    # BOOLEAN
        out += _vint_field(10, 1 if value else 0)
    elif atype == 11:   # LONGS
        for v in value:
            out += _vint_field(15, v)
    else:
        raise ValueError(atype)
    return out


def _op(op_type, inputs, outputs, attrs=()):
    out = b''
    for param, args in inputs:
        var = _str_field(1, param)
        for a in args:
            var += _str_field(2, a)
        out += _len_field(1, var)
    for param, args in outputs:
        var = _str_field(1, param)
        for a in args:
            var += _str_field(2, a)
        out += _len_field(2, var)
    out += _str_field(3, op_type)
    for a in attrs:
        out += _len_field(4, _attr(*a))
    return out


_FP32 = 5


def _tensor_desc(dtype, dims):
    out = _vint_field(1, dtype)
    for d in dims:
        out += _vint_field(2, d)
    return out


def _var(name, dims=None, vtype=7, dtype=_FP32, persistable=False):
    """vtype 7 = LOD_TENSOR, 9 = FEED_MINIBATCH, 10 = FETCH_LIST."""
    vt = _vint_field(1, vtype)
    if dims is not None:
        lod = _len_field(1, _tensor_desc(dtype, dims)) + _vint_field(2, 0)
        vt += _len_field(3, lod)
    out = _str_field(1, name) + _len_field(2, vt)
    if persistable:
        out += _vint_field(3, 1)
    return out


def _block(variables, ops, idx=0, parent=-1):
    out = _vint_field(1, idx) + _vint_field(2, parent)
    for v in variables:
        out += _len_field(3, v)
    for o in ops:
        out += _len_field(4, o)
    return out


def _program(blocks):
    out = b''
    for b in blocks:
        out += _len_field(1, b)
    out += _len_field(4, _vint_field(1, 0))  # Version{version=0}
    return out


def _write_lod_tensor(f, arr):
    """lod_tensor.cc SerializeToStream: u32 ver, u64 lod levels, then
    tensor_util.cc TensorToStream: u32 ver, i32 desc size, desc, data."""
    f.write(struct.pack('<I', 0))
    f.write(struct.pack('<Q', 0))
    desc = _tensor_desc(_FP32, arr.shape)
    f.write(struct.pack('<I', 0))
    f.write(struct.pack('<i', len(desc)))
    f.write(desc)
    f.write(np.ascontiguousarray(arr, np.float32).tobytes())


# -- model builders -----------------------------------------------------------

def _fit_a_line_dir(tmp_path, combined):
    rng = np.random.RandomState(0)
    w = rng.randn(13, 1).astype(np.float32)
    b = rng.randn(1).astype(np.float32)

    variables = [
        _var('feed', vtype=9, persistable=True),
        _var('fetch', vtype=10, persistable=True),
        _var('x', dims=[-1, 13]),
        _var('fc_w', dims=[13, 1], persistable=True),
        _var('fc_b', dims=[1], persistable=True),
        _var('fc_tmp', dims=[-1, 1]),
        _var('out', dims=[-1, 1]),
    ]
    ops = [
        _op('feed', [('X', ['feed'])], [('Out', ['x'])],
            [('col', 0, 0)]),
        _op('mul', [('X', ['x']), ('Y', ['fc_w'])],
            [('Out', ['fc_tmp'])],
            [('x_num_col_dims', 0, 1), ('y_num_col_dims', 0, 1)]),
        _op('elementwise_add', [('X', ['fc_tmp']), ('Y', ['fc_b'])],
            [('Out', ['out'])], [('axis', 0, 1)]),
        _op('fetch', [('X', ['out'])], [('Out', ['fetch'])],
            [('col', 0, 0)]),
    ]
    d = tmp_path / ('fit_a_line_comb' if combined else 'fit_a_line')
    d.mkdir()
    (d / '__model__').write_bytes(_program([_block(variables, ops)]))
    params = {'fc_w': w, 'fc_b': b}
    if combined:
        with open(d / '__params__', 'wb') as f:
            for name in sorted(params):
                _write_lod_tensor(f, params[name])
    else:
        for name, arr in params.items():
            with open(d / name, 'wb') as f:
                _write_lod_tensor(f, arr)
    return d, w, b


def _digits_cnn_dir(tmp_path):
    """recognize_digits-style: conv2d -> batch_norm -> relu -> pool2d ->
    flatten -> fc(mul+add) -> softmax."""
    rng = np.random.RandomState(1)
    conv_w = (rng.randn(4, 1, 3, 3) * 0.5).astype(np.float32)
    bn_scale = rng.rand(4).astype(np.float32) + 0.5
    bn_bias = rng.randn(4).astype(np.float32)
    bn_mean = rng.randn(4).astype(np.float32) * 0.1
    bn_var = rng.rand(4).astype(np.float32) + 0.5
    fc_w = (rng.randn(4 * 13 * 13, 10) * 0.1).astype(np.float32)
    fc_b = rng.randn(10).astype(np.float32)

    variables = [
        _var('feed', vtype=9, persistable=True),
        _var('fetch', vtype=10, persistable=True),
        _var('img', dims=[-1, 1, 28, 28]),
        _var('conv_w', dims=[4, 1, 3, 3], persistable=True),
        _var('bn_scale', dims=[4], persistable=True),
        _var('bn_bias', dims=[4], persistable=True),
        _var('bn_mean', dims=[4], persistable=True),
        _var('bn_var', dims=[4], persistable=True),
        _var('fc_w', dims=[4 * 13 * 13, 10], persistable=True),
        _var('fc_b', dims=[10], persistable=True),
        _var('conv_out', dims=[-1, 4, 26, 26]),
        _var('bn_out', dims=[-1, 4, 26, 26]),
        _var('relu_out', dims=[-1, 4, 26, 26]),
        _var('pool_out', dims=[-1, 4, 13, 13]),
        _var('flat_out', dims=[-1, 4 * 13 * 13]),
        _var('fc_tmp', dims=[-1, 10]),
        _var('fc_out', dims=[-1, 10]),
        _var('prob', dims=[-1, 10]),
    ]
    ops = [
        _op('feed', [('X', ['feed'])], [('Out', ['img'])], [('col', 0, 0)]),
        _op('conv2d', [('Input', ['img']), ('Filter', ['conv_w'])],
            [('Output', ['conv_out'])],
            [('strides', 3, [1, 1]), ('paddings', 3, [0, 0]),
             ('dilations', 3, [1, 1]), ('groups', 0, 1)]),
        _op('batch_norm',
            [('X', ['conv_out']), ('Scale', ['bn_scale']),
             ('Bias', ['bn_bias']), ('Mean', ['bn_mean']),
             ('Variance', ['bn_var'])],
            [('Y', ['bn_out'])],
            [('epsilon', 1, 1e-5), ('is_test', 6, True)]),
        _op('relu', [('X', ['bn_out'])], [('Out', ['relu_out'])]),
        _op('pool2d', [('X', ['relu_out'])], [('Out', ['pool_out'])],
            [('pooling_type', 2, 'max'), ('ksize', 3, [2, 2]),
             ('strides', 3, [2, 2]), ('paddings', 3, [0, 0])]),
        _op('flatten_contiguous_range', [('X', ['pool_out'])],
            [('Out', ['flat_out'])],
            [('start_axis', 0, 1), ('stop_axis', 0, -1)]),
        _op('mul', [('X', ['flat_out']), ('Y', ['fc_w'])],
            [('Out', ['fc_tmp'])],
            [('x_num_col_dims', 0, 1), ('y_num_col_dims', 0, 1)]),
        _op('elementwise_add', [('X', ['fc_tmp']), ('Y', ['fc_b'])],
            [('Out', ['fc_out'])], [('axis', 0, 1)]),
        _op('softmax', [('X', ['fc_out'])], [('Out', ['prob'])],
            [('axis', 0, -1)]),
        _op('fetch', [('X', ['prob'])], [('Out', ['fetch'])],
            [('col', 0, 0)]),
    ]
    d = tmp_path / 'digits'
    d.mkdir()
    (d / '__model__').write_bytes(_program([_block(variables, ops)]))
    params = {'conv_w': conv_w, 'bn_scale': bn_scale, 'bn_bias': bn_bias,
              'bn_mean': bn_mean, 'bn_var': bn_var, 'fc_w': fc_w,
              'fc_b': fc_b}
    for name, arr in params.items():
        with open(d / name, 'wb') as f:
            _write_lod_tensor(f, arr)
    return d, params


def _np_conv2d(x, w):
    n, cin, h, ww = x.shape
    cout, _, kh, kw = w.shape
    oh, ow = h - kh + 1, ww - kw + 1
    out = np.zeros((n, cout, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = x[:, :, i:i + kh, j:j + kw]          # n,cin,kh,kw
            out[:, :, i, j] = np.einsum('ncij,ocij->no', patch, w)
    return out


def _np_maxpool2(x):
    n, c, h, w = x.shape
    return x.reshape(n, c, h // 2, 2, w // 2, 2).max(axis=(3, 5))


# -- tests --------------------------------------------------------------------

@pytest.mark.parametrize('combined', [False, True])
def test_fit_a_line_reference_model_serves(tmp_path, combined):
    d, w, b = _fit_a_line_dir(tmp_path, combined)
    cfg = Config(str(d))
    if combined:
        cfg.set_model(str(d / '__model__'), str(d / '__params__'))
    pred = create_predictor(cfg)
    assert pred.get_input_names() == ['x']
    rng = np.random.RandomState(2)
    x = rng.randn(5, 13).astype(np.float32)
    out, = pred.run([x])
    np.testing.assert_allclose(out, x @ w + b, rtol=1e-5, atol=1e-6)


def test_digits_cnn_reference_model_serves(tmp_path):
    d, p = _digits_cnn_dir(tmp_path)
    pred = create_predictor(Config(str(d)))
    rng = np.random.RandomState(3)
    x = rng.rand(2, 1, 28, 28).astype(np.float32)
    out, = pred.run([x])

    conv = _np_conv2d(x, p['conv_w'])
    sh = (1, -1, 1, 1)
    bn = ((conv - p['bn_mean'].reshape(sh)) /
          np.sqrt(p['bn_var'].reshape(sh) + 1e-5) *
          p['bn_scale'].reshape(sh) + p['bn_bias'].reshape(sh))
    act = np.maximum(bn, 0)
    pool = _np_maxpool2(act)
    flat = pool.reshape(2, -1)
    logits = flat @ p['fc_w'] + p['fc_b']
    e = np.exp(logits - logits.max(-1, keepdims=True))
    ref = e / e.sum(-1, keepdims=True)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_loader_direct_api_and_trailing_byte_guard(tmp_path):
    d, w, b = _fit_a_line_dir(tmp_path, combined=True)
    prog = load_fluid_model(str(d / '__model__'), str(d / '__params__'))
    assert prog.feed_names == ['x'] and len(prog.params) == 2
    np.testing.assert_array_equal(prog.params['fc_w'], w)
    # corrupt: append a byte -> loader must refuse (ordering mismatch
    # would otherwise silently misassign tensors)
    with open(d / '__params__', 'ab') as f:
        f.write(b'\x00')
    with pytest.raises(ValueError, match='trailing'):
        load_fluid_model(str(d / '__model__'), str(d / '__params__'))


def test_executor_load_inference_model_serves_reference_dir(tmp_path):
    """The fluid-era path: static.load_inference_model on a reference
    model dir + Executor.run (the reference book tests' serving idiom)."""
    import paddle_tpu as paddle
    from paddle_tpu import static

    d, w, b = _fit_a_line_dir(tmp_path, combined=False)
    exe = static.Executor()
    prog, feeds, fetches = static.load_inference_model(str(d), exe)
    assert feeds == ['x']
    rng = np.random.RandomState(4)
    x = rng.randn(3, 13).astype(np.float32)
    out, = exe.run(prog, feed={'x': x}, fetch_list=fetches)
    np.testing.assert_allclose(out, x @ w + b, rtol=1e-5, atol=1e-6)


def test_extended_op_table_executes(tmp_path):
    """CNN-era ops beyond the book models: leaky_relu(alpha),
    layer_norm, nearest_interp_v2, pad2d, split + stack — vs numpy."""
    variables = [
        _var('feed', vtype=9, persistable=True),
        _var('fetch', vtype=10, persistable=True),
        _var('x', dims=[-1, 2, 4, 4]),
        _var('lr_out', dims=[-1, 2, 4, 4]),
        _var('up', dims=[-1, 2, 8, 8]),
        _var('padded', dims=[-1, 2, 10, 10]),
        _var('s0', dims=[-1, 1, 10, 10]),
        _var('s1', dims=[-1, 1, 10, 10]),
        _var('stacked', dims=[-1, 2, 1, 10, 10]),
    ]
    ops = [
        _op('feed', [('X', ['feed'])], [('Out', ['x'])], [('col', 0, 0)]),
        _op('leaky_relu', [('X', ['x'])], [('Out', ['lr_out'])],
            [('alpha', 1, 0.1)]),
        _op('nearest_interp_v2', [('X', ['lr_out'])], [('Out', ['up'])],
            [('out_h', 0, 8), ('out_w', 0, 8),
             ('align_corners', 6, False)]),
        _op('pad2d', [('X', ['up'])], [('Out', ['padded'])],
            [('paddings', 3, [1, 1, 1, 1]), ('mode', 2, 'constant'),
             ('pad_value', 1, 0.0)]),
        _op('split', [('X', ['padded'])], [('Out', ['s0', 's1'])],
            [('axis', 0, 1), ('num', 0, 2)]),
        _op('stack', [('X', ['s0', 's1'])], [('Y', ['stacked'])],
            [('axis', 0, 1)]),
        _op('fetch', [('X', ['stacked'])], [('Out', ['fetch'])],
            [('col', 0, 0)]),
    ]
    d = tmp_path / 'ext_ops'
    d.mkdir()
    (d / '__model__').write_bytes(_program([_block(variables, ops)]))
    prog = load_fluid_model(str(d))
    rng = np.random.RandomState(6)
    x = rng.randn(2, 2, 4, 4).astype(np.float32)
    out, = prog.run({'x': x})

    ref = np.where(x > 0, x, 0.1 * x)
    ref = ref.repeat(2, axis=2).repeat(2, axis=3)      # nearest 2x
    ref = np.pad(ref, [(0, 0), (0, 0), (1, 1), (1, 1)])
    parts = np.split(ref, 2, axis=1)
    ref = np.stack(parts, axis=1)
    assert out.shape == (2, 2, 1, 10, 10)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_yolo_detection_ops_serve(tmp_path):
    """The real exported PP-YOLO tail — yolo_box (scores [N,M,C]) ->
    transpose2 ([N,C,M]) -> multiclass_nms3 — through the fluid table,
    matching the native vision implementations (themselves
    reference-validated in test_yolo.py)."""
    na, cls, h = 3, 4, 4
    c = na * (5 + cls)
    anchors = [10, 13, 16, 30, 33, 23]
    variables = [
        _var('feed', vtype=9, persistable=True),
        _var('fetch', vtype=10, persistable=True),
        _var('head', dims=[-1, c, h, h]),
        _var('imgsz', dims=[-1, 2], dtype=2),       # int32
        _var('boxes', dims=[-1, na * h * h, 4]),
        _var('scores_mc', dims=[-1, na * h * h, cls]),
        _var('scores', dims=[-1, cls, na * h * h]),
        _var('dets', dims=[-1, 6]),
        _var('rois_n', dims=[-1], dtype=2),
    ]
    ops = [
        _op('feed', [('X', ['feed'])], [('Out', ['head'])],
            [('col', 0, 0)]),
        _op('feed', [('X', ['feed'])], [('Out', ['imgsz'])],
            [('col', 0, 1)]),
        _op('yolo_box', [('X', ['head']), ('ImgSize', ['imgsz'])],
            [('Boxes', ['boxes']), ('Scores', ['scores_mc'])],
            [('anchors', 3, anchors), ('class_num', 0, cls),
             ('conf_thresh', 1, 0.01), ('downsample_ratio', 0, 32),
             ('clip_bbox', 6, True), ('scale_x_y', 1, 1.0)]),
        _op('transpose2', [('X', ['scores_mc'])], [('Out', ['scores'])],
            [('axis', 3, [0, 2, 1])]),
        _op('multiclass_nms3',
            [('BBoxes', ['boxes']), ('Scores', ['scores'])],
            [('Out', ['dets']), ('NmsRoisNum', ['rois_n'])],
            [('score_threshold', 1, 0.01), ('nms_top_k', 0, 10),
             ('keep_top_k', 0, 5), ('nms_threshold', 1, 0.45),
             ('normalized', 6, True), ('background_label', 0, -1)]),
        _op('fetch', [('X', ['dets'])], [('Out', ['fetch'])],
            [('col', 0, 0)]),
        _op('fetch', [('X', ['rois_n'])], [('Out', ['fetch'])],
            [('col', 0, 1)]),
    ]
    d = tmp_path / 'yolo_tail'
    d.mkdir()
    (d / '__model__').write_bytes(_program([_block(variables, ops)]))
    prog = load_fluid_model(str(d))
    rng = np.random.RandomState(8)
    head = rng.randn(1, c, h, h).astype(np.float32)
    imgsz = np.array([[128, 128]], np.int32)
    dets, rois_n = prog.run({'head': head, 'imgsz': imgsz})

    import paddle_tpu as paddle
    from paddle_tpu.vision.ops import yolo_box
    from paddle_tpu.vision.detection import multiclass_nms
    b_ref, s_ref = yolo_box(paddle.to_tensor(head),
                            paddle.to_tensor(imgsz), anchors=anchors,
                            class_num=cls, conf_thresh=0.01,
                            downsample_ratio=32)
    s_ref_cm = paddle.transpose(s_ref, [0, 2, 1])  # [N,M,C] -> [N,C,M]
    out_ref, rois_ref = multiclass_nms(
        b_ref, s_ref_cm, score_threshold=0.01, nms_top_k=10, keep_top_k=5,
        nms_threshold=0.45, background_label=-1, return_rois_num=True)
    np.testing.assert_allclose(dets, out_ref.numpy(), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(rois_n, rois_ref.numpy())


def test_optim_cache_dir_persists_executables(tmp_path, monkeypatch):
    """Config.set_optim_cache_dir -> jax persistent compilation cache:
    running the predictor populates the directory with compiled
    executables (restart-warm serving)."""
    import jax
    # a cache placed from outside would win over the Predictor's dir
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    d, w, b = _fit_a_line_dir(tmp_path, combined=False)
    cache = tmp_path / 'optim_cache'
    cfg = Config(str(d))
    cfg.set_optim_cache_dir(str(cache))
    try:
        pred = create_predictor(cfg)
        x = np.random.RandomState(1).randn(2, 13).astype(np.float32)
        out, = pred.run([x])
        np.testing.assert_allclose(out, x @ w + b, rtol=1e-5, atol=1e-6)
        assert cache.exists() and any(cache.iterdir()), \
            'persistent cache dir not populated'
    finally:
        # the knob is process-global; later tests must not write compile
        # artifacts into this (soon-deleted) tmp dir
        jax.config.update('jax_compilation_cache_dir', None)


def test_rcnn_family_ops_serve(tmp_path):
    """roi_align (RoisNum batching) + box_coder via the fluid table match
    the native vision implementations."""
    variables = [
        _var('feed', vtype=9, persistable=True),
        _var('fetch', vtype=10, persistable=True),
        _var('feat', dims=[-1, 3, 8, 8]),
        _var('rois', dims=[-1, 4]),
        _var('rois_num', dims=[-1], dtype=2),
        _var('pooled', dims=[-1, 3, 2, 2]),
    ]
    ops = [
        _op('feed', [('X', ['feed'])], [('Out', ['feat'])],
            [('col', 0, 0)]),
        _op('feed', [('X', ['feed'])], [('Out', ['rois'])],
            [('col', 0, 1)]),
        _op('feed', [('X', ['feed'])], [('Out', ['rois_num'])],
            [('col', 0, 2)]),
        _op('roi_align',
            [('X', ['feat']), ('ROIs', ['rois']),
             ('RoisNum', ['rois_num'])],
            [('Out', ['pooled'])],
            [('pooled_height', 0, 2), ('pooled_width', 0, 2),
             ('spatial_scale', 1, 0.5), ('sampling_ratio', 0, 2),
             ('aligned', 6, True)]),
        _op('fetch', [('X', ['pooled'])], [('Out', ['fetch'])],
            [('col', 0, 0)]),
    ]
    d = tmp_path / 'rcnn'
    d.mkdir()
    (d / '__model__').write_bytes(_program([_block(variables, ops)]))
    prog = load_fluid_model(str(d))
    rng = np.random.RandomState(9)
    feat = rng.randn(2, 3, 8, 8).astype(np.float32)
    rois = np.abs(rng.randn(4, 4)).astype(np.float32) * 4
    rois[:, 2:] += rois[:, :2] + 2
    rois_num = np.array([3, 1], np.int32)
    out, = prog.run({'feat': feat, 'rois': rois, 'rois_num': rois_num})

    import paddle_tpu as paddle
    from paddle_tpu.vision.ops import roi_align
    ref = roi_align(paddle.to_tensor(feat), paddle.to_tensor(rois),
                    paddle.to_tensor(rois_num), output_size=2,
                    spatial_scale=0.5, sampling_ratio=2, aligned=True)
    np.testing.assert_allclose(out, ref.numpy(), rtol=1e-5, atol=1e-5)


def test_parser_roundtrips_negative_and_attr_types(tmp_path):
    blk = _block([_var('v', dims=[-1, 7])],
                 [_op('scale', [('X', ['v'])], [('Out', ['v2'])],
                      [('scale', 1, 2.5), ('bias', 1, -1.0),
                       ('bias_after_scale', 6, True)]),
                  _op('reshape2', [('X', ['v2'])], [('Out', ['v3'])],
                      [('shape', 3, [-1, 7])]),
                  _op('slice', [('Input', ['v3'])], [('Out', ['v4'])],
                      [('axes', 3, [0]), ('starts', 3, [0]),
                       ('ends', 3, [1]), ('decrease_axis', 3, [0])])])
    blocks = parse_program_desc(_program([blk]))
    v = blocks[0].vars['v']
    assert v.shape == [-1, 7]
    op = blocks[0].ops[0]
    assert op.type == 'scale'
    assert op.attr('scale') == pytest.approx(2.5)
    assert op.attr('bias') == pytest.approx(-1.0)
    assert op.attr('bias_after_scale') is True
    # negative INTS arrive sign-extended as 64-bit varints (proto2):
    # the common reshape2(shape=[-1, C]) case must decode to -1
    assert blocks[0].ops[1].attr('shape') == [-1, 7]
    assert blocks[0].ops[2].attr('decrease_axis') == [0]


def test_reshape_neg1_and_decrease_axis_execute(tmp_path):
    """End-to-end: a program using reshape2([-1, C]) and a
    decrease_axis slice runs and matches numpy."""
    variables = [
        _var('feed', vtype=9, persistable=True),
        _var('fetch', vtype=10, persistable=True),
        _var('x', dims=[-1, 2, 6]),
        _var('r', dims=[-1, 6]),
        _var('row', dims=[6]),
    ]
    ops = [
        _op('feed', [('X', ['feed'])], [('Out', ['x'])], [('col', 0, 0)]),
        _op('reshape2', [('X', ['x'])], [('Out', ['r'])],
            [('shape', 3, [-1, 6])]),
        _op('slice', [('Input', ['r'])], [('Out', ['row'])],
            [('axes', 3, [0]), ('starts', 3, [0]), ('ends', 3, [1]),
             ('decrease_axis', 3, [0])]),
        _op('fetch', [('X', ['row'])], [('Out', ['fetch'])],
            [('col', 0, 0)]),
    ]
    d = tmp_path / 'negshape'
    d.mkdir()
    (d / '__model__').write_bytes(_program([_block(variables, ops)]))
    prog = load_fluid_model(str(d))
    rng = np.random.RandomState(5)
    x = rng.randn(2, 2, 6).astype(np.float32)
    out, = prog.run({'x': x})
    assert out.shape == (6,)
    np.testing.assert_allclose(out, x.reshape(-1, 6)[0], rtol=1e-6)


def _word2vec_dir(tmp_path):
    """The word2vec book-test graph (test_word2vec_book.py shape): four
    context words share ONE embedding table (lookup_table_v2), concat,
    fc, softmax over the vocab."""
    rng = np.random.RandomState(7)
    vocab, emb, n_ctx = 50, 8, 4
    table = rng.randn(vocab, emb).astype(np.float32)
    fc_w = rng.randn(n_ctx * emb, vocab).astype(np.float32)
    fc_b = rng.randn(vocab).astype(np.float32)

    int64 = 3
    variables = [
        _var('feed', vtype=9, persistable=True),
        _var('fetch', vtype=10, persistable=True),
        _var('emb_table', dims=[vocab, emb], persistable=True),
        _var('fc_w', dims=[n_ctx * emb, vocab], persistable=True),
        _var('fc_b', dims=[vocab], persistable=True),
        _var('cat', dims=[-1, n_ctx * emb]),
        _var('fc_tmp', dims=[-1, vocab]),
        _var('logits', dims=[-1, vocab]),
        _var('prob', dims=[-1, vocab]),
    ]
    ops = []
    for i in range(n_ctx):
        variables.append(_var('w%d' % i, dims=[-1], dtype=int64))
        variables.append(_var('emb%d' % i, dims=[-1, emb]))
        ops.append(_op('feed', [('X', ['feed'])], [('Out', ['w%d' % i])],
                       [('col', 0, i)]))
    for i in range(n_ctx):
        ops.append(_op('lookup_table_v2',
                       [('Ids', ['w%d' % i]), ('W', ['emb_table'])],
                       [('Out', ['emb%d' % i])]))
    ops += [
        _op('concat', [('X', ['emb%d' % i for i in range(n_ctx)])],
            [('Out', ['cat'])], [('axis', 0, 1)]),
        _op('mul', [('X', ['cat']), ('Y', ['fc_w'])],
            [('Out', ['fc_tmp'])],
            [('x_num_col_dims', 0, 1), ('y_num_col_dims', 0, 1)]),
        _op('elementwise_add', [('X', ['fc_tmp']), ('Y', ['fc_b'])],
            [('Out', ['logits'])], [('axis', 0, 1)]),
        _op('softmax', [('X', ['logits'])], [('Out', ['prob'])],
            [('axis', 0, -1)]),
        _op('fetch', [('X', ['prob'])], [('Out', ['fetch'])],
            [('col', 0, 0)]),
    ]
    d = tmp_path / 'word2vec'
    d.mkdir()
    (d / '__model__').write_bytes(_program([_block(variables, ops)]))
    for name, arr in (('emb_table', table), ('fc_w', fc_w),
                      ('fc_b', fc_b)):
        with open(d / name, 'wb') as f:
            _write_lod_tensor(f, arr)
    return d, table, fc_w, fc_b


def test_word2vec_reference_model_serves(tmp_path):
    d, table, fc_w, fc_b = _word2vec_dir(tmp_path)
    pred = create_predictor(Config(str(d)))
    assert pred.get_input_names() == ['w0', 'w1', 'w2', 'w3']
    rng = np.random.RandomState(8)
    ids = [rng.randint(0, 50, (6,)).astype(np.int64) for _ in range(4)]
    out, = pred.run(ids)
    cat = np.concatenate([table[i] for i in ids], axis=1)
    logits = cat @ fc_w + fc_b
    e = np.exp(logits - logits.max(-1, keepdims=True))
    ref = e / e.sum(-1, keepdims=True)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
