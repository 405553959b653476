"""Explicit forward-smoke inputs for ops the generic probe can't drive.

Shared by tests/test_op_coverage.py (every-registered-op forward oracle,
the check_consistency companion) and usable by benchmark/opperf.  Each
entry: name -> (list of np arrays (float32 unless noted), attrs dict).
"""
import numpy as onp

_R = onp.random.RandomState(7)


def _f(*shape):
    return (_R.rand(*shape).astype(onp.float32) + 0.1)


def _i(hi, *shape):
    return _R.randint(0, hi, shape).astype(onp.int32)


def _psd(n):
    a = _R.rand(n, n).astype(onp.float32)
    return a @ a.T + n * onp.eye(n, dtype=onp.float32)


def _tri(n):
    return onp.tril(_R.rand(n, n).astype(onp.float32) + 0.5)


_SQ = _f(5, 5)
_CONV = dict(kernel=(3, 3), num_filter=8)

SPECS = {
    # --- nn -------------------------------------------------------------
    "Convolution": ([_f(2, 4, 8, 8), _f(8, 4, 3, 3), _f(8)], _CONV),
    "Deconvolution": ([_f(2, 8, 6, 6), _f(8, 4, 3, 3), _f(4)],
                      dict(kernel=(3, 3), num_filter=4)),
    "BatchNorm": ([_f(2, 4, 6, 6), _f(4), _f(4), _f(4), _f(4)], {}),
    "GroupNorm": ([_f(2, 4, 6, 6), _f(4), _f(4)], dict(num_groups=2)),
    "InstanceNorm": ([_f(2, 4, 6, 6), _f(4), _f(4)], {}),
    "Dropout": ([_f(4, 6), onp.zeros(2, onp.uint32)], dict(p=0.5)),
    "LayerNorm": ([_f(4, 8), _f(8), _f(8)], {}),
    "FullyConnected": ([_f(4, 8), _f(16, 8), _f(16)],
                       dict(num_hidden=16)),
    "Pooling": ([_f(2, 4, 8, 8)], dict(kernel=(2, 2), pool_type="max")),
    "AdaptiveAvgPooling2D": ([_f(2, 4, 8, 8)], dict(output_size=2)),
    "BilinearResize2D": ([_f(2, 3, 8, 8)], dict(height=4, width=4)),
    "UpSampling": ([_f(2, 3, 4, 4)], dict(scale=2, sample_type="nearest")),
    "CTCLoss": ([_f(8, 2, 10), _i(9, 2, 4).astype(onp.float32)], {}),
    "_rnn_fused": ([_f(5, 2, 4), _f(1, 2, 8), _f(1, 2, 8),
                    _f(32, 4), _f(32, 8), _f(32), _f(32)],
                   dict(hidden_size=8, num_layers=1, mode="lstm")),
    "ROIAlign": ([_f(1, 4, 8, 8),
                  onp.asarray([[0, 1, 1, 6, 6]], onp.float32)],
                 dict(pooled_size=(2, 2), spatial_scale=1.0)),
    "PSROIPooling": ([_f(1, 8, 8, 8),
                      onp.asarray([[0, 1, 1, 6, 6]], onp.float32)],
                     dict(output_dim=2, pooled_size=2, spatial_scale=1.0)),
    "BilinearSampler": ([_f(1, 2, 6, 6),
                         (_R.rand(1, 2, 4, 4) * 2 - 1).astype(onp.float32)],
                        {}),
    "SpatialTransformer": ([_f(1, 2, 6, 6),
                            onp.asarray([[1, 0, 0, 0, 1, 0]], onp.float32)],
                           dict(target_shape=(6, 6))),
    "GridGenerator": ([onp.asarray([[1, 0, 0, 0, 1, 0]], onp.float32)],
                      dict(transform_type="affine", target_shape=(4, 4))),
    "DeformableConvolution": ([_f(1, 4, 7, 7), onp.zeros((1, 18, 5, 5),
                                                         onp.float32),
                               _f(6, 4, 3, 3), _f(6)],
                              dict(kernel=(3, 3), num_filter=6)),
    "ModulatedDeformableConvolution": (
        [_f(1, 4, 7, 7), onp.zeros((1, 18, 5, 5), onp.float32),
         onp.full((1, 9, 5, 5), 0.5, onp.float32), _f(6, 4, 3, 3), _f(6)],
        dict(kernel=(3, 3), num_filter=6)),
    "Correlation": ([_f(1, 4, 6, 6), _f(1, 4, 6, 6)],
                    dict(max_displacement=1, pad_size=1)),
    "Crop": ([_f(1, 2, 6, 6)], dict(h_w=(4, 4), center_crop=True)),
    "depth_to_space": ([_f(1, 8, 3, 3)], dict(block_size=2)),
    "space_to_depth": ([_f(1, 2, 6, 6)], dict(block_size=2)),
    "Proposal": ([_f(1, 6, 4, 4), _f(1, 12, 4, 4),
                  onp.asarray([[32, 32, 1.0]], onp.float32)],
                 dict(scales=(8.0,), ratios=(0.5, 1.0, 2.0),
                      feature_stride=8, rpn_post_nms_top_n=5)),
    # --- attention ------------------------------------------------------
    "interleaved_matmul_selfatt_qk": ([_f(6, 2, 24)], dict(heads=2)),
    "interleaved_selfatt": ([_f(8, 2, 96), onp.zeros(2, onp.uint32)],
                            dict(heads=2, p=0.5, training=True)),
    "causal_gqa_selfatt": ([_f(2, 8, 32), _f(2, 8, 16), _f(2, 8, 16)],
                           dict(heads=4, kv_heads=2)),
    # latent attention: 2 heads of [6 nope; 2 rope] queries and [6; 5]
    # keys and values, one rotary key of 2 a token
    "causal_latent_selfatt": ([_f(2, 8, 16), _f(2, 8, 22), _f(2, 8, 2)],
                              dict(heads=2, rope_dim=2, theta=100.0)),
    "rope": ([_f(2, 8, 3, 4)], dict(theta=100.0)),
    # --- state-space and sparse-expert layers (ops/ssm.py, parallel/moe.py)
    "ssd_scan": ([_f(2, 12, 4, 8), _f(2, 12, 4), -_f(4), _f(2, 12, 2, 16),
                  _f(2, 12, 2, 16), _f(4)], dict(chunk_size=8)),
    "causal_conv1d": ([_f(2, 9, 6), _f(6, 4), _f(6)],
                      dict(activation="silu")),
    "RMSNorm": ([_f(4, 6), _f(6)], {}),
    "GatedRMSNorm": ([_f(4, 8), _f(4, 8), _f(8)], dict(num_groups=2)),
    # the gated delta rule: 3 heads of keys of 8 and values of 12, two
    # chunks and a part; log alpha at most 0, beta under 2
    "gated_delta_rule": ([_f(2, 20, 3, 8) * 0.3, _f(2, 20, 3, 8) * 0.3,
                          _f(2, 20, 3, 12), -_f(2, 20, 3), _f(2, 20, 3)],
                         dict(chunk_size=8)),
    "held_experts": ([_f(10, 16), _f(8, 16), _f(8) * 0.05, _f(4, 16, 12),
                      _f(4, 12, 16)],
                     dict(held=(0, 1, 2, 3), k=2, scaling=2.5)),
    "interleaved_matmul_selfatt_valatt": ([_f(6, 2, 24), _f(4, 6, 6)],
                                          dict(heads=2)),
    "interleaved_matmul_encdec_qk": ([_f(6, 2, 8), _f(5, 2, 16)],
                                     dict(heads=2)),
    "interleaved_matmul_encdec_valatt": ([_f(5, 2, 16), _f(4, 6, 5)],
                                         dict(heads=2)),
    # --- tensor/shape ---------------------------------------------------
    "reshape": ([_f(4, 6)], dict(shape=(6, 4))),
    "npx_reshape": ([_f(2, 3, 8)], dict(newshape=(-2, -2, 2, -1))),
    "Reshape": ([_f(4, 6)], dict(shape=(6, 4))),
    "slice": ([_f(4, 6)], dict(begin=(0, 1), end=(3, 5))),
    "reverse": ([_f(4, 6)], dict(axis=0)),
    "roll": ([_f(4, 6)], dict(shift=2)),
    "tile": ([_f(2, 3)], dict(reps=(2, 2))),
    "pad": ([_f(1, 2, 4, 4)],
            dict(mode="constant", pad_width=(0, 0, 0, 0, 1, 1, 1, 1))),
    "broadcast_axis": ([_f(1, 6)], dict(axis=0, size=4)),
    "broadcast_to": ([_f(1, 6)], dict(shape=(4, 6))),
    "ones": ([], dict(shape=(3, 3))),
    "zeros": ([], dict(shape=(3, 3))),
    "full": ([], dict(shape=(3, 3), value=2.5)),
    "pick": ([_f(4, 6), _i(6, 4).astype(onp.float32)], {}),
    "batch_take": ([_f(4, 6), _i(6, 4)], {}),
    "choose_element_0index": ([_f(4, 6), _i(6, 4)], {}),
    "fill_element_0index": ([_f(4, 6), _f(4), _i(6, 4)], {}),
    "gather_nd": ([_f(4, 6), _i(4, 1, 3)], {}),
    "scatter_nd": ([_f(3), onp.stack([_i(4, 3), _i(6, 3)]).astype(
        onp.int32)], dict(shape=(4, 6))),
    "index_copy": ([_f(4, 6), _i(4, 2), _f(2, 6)], {}),
    "unravel_index": ([_i(24, 5)], dict(shape=(4, 6))),
    "ravel_multi_index": ([onp.stack([_i(4, 5), _i(6, 5)]).astype(
        onp.int32)], dict(shape=(4, 6))),
    "one_hot": ([_i(6, 4)], dict(depth=6)),
    "topk": ([_f(4, 6)], dict(k=2)),
    "sequence_mask": ([_f(5, 2, 4), onp.asarray([3, 5], onp.float32)],
                      dict(use_sequence_length=True)),
    "sequence_last": ([_f(5, 2, 4), onp.asarray([3, 5], onp.float32)],
                      dict(use_sequence_length=True)),
    "sequence_reverse": ([_f(5, 2, 4), onp.asarray([3, 5], onp.float32)],
                         dict(use_sequence_length=True)),
    "SwapAxis": ([_f(4, 6)], dict(dim1=0, dim2=1)),
    "expand_dims": ([_f(4, 6)], dict(axis=0)),
    "squeeze": ([_f(1, 4, 6)], dict(axis=0)),
    # --- matmul/linalg --------------------------------------------------
    "dot": ([_f(4, 6), _f(6, 5)], {}),
    "batch_dot": ([_f(2, 4, 6), _f(2, 6, 5)], {}),
    "matmul": ([_f(4, 6), _f(6, 5)], {}),
    "linalg_gemm": ([_f(4, 6), _f(6, 5), _f(4, 5)], {}),
    "linalg_gemm2": ([_f(4, 6), _f(6, 5)], {}),
    "linalg_cholesky": ([_psd(5)], {}),
    "linalg_potrf": ([_psd(5)], {}),
    "linalg_potri": ([_tri(5)], {}),
    "linalg_det": ([_SQ], {}),
    "linalg_slogdet": ([_psd(5)], {}),
    "linalg_inverse": ([_psd(5)], {}),
    "linalg_eigh": ([_psd(5)], {}),
    "linalg_eigvalsh": ([_psd(5)], {}),
    "linalg_solve": ([_psd(5), _f(5, 3)], {}),
    "linalg_trmm": ([_tri(5), _f(5, 3)], {}),
    "linalg_trsm": ([_tri(5), _f(5, 3)], {}),
    "linalg_tensorinv": ([_psd(4).reshape(2, 2, 2, 2)], dict(ind=2)),
    "linalg_syrk": ([_f(4, 6)], {}),
    "linalg_extracttrian": ([_SQ], {}),
    "linalg_makediag": ([_f(5)], {}),
    "linalg_maketrian": ([_f(15)], {}),
    "linalg_extractdiag": ([_SQ], {}),
    # --- detection ------------------------------------------------------
    "box_iou": ([_R.rand(4, 4).astype(onp.float32),
                 _R.rand(5, 4).astype(onp.float32)], {}),
    "box_encode": ([onp.ones((1, 3), onp.float32),
                    onp.zeros((1, 3), onp.float32),
                    onp.asarray([[[.1, .1, .4, .5], [.2, .2, .6, .7],
                                  [.3, .1, .8, .4]]], onp.float32),
                    onp.asarray([[[.15, .15, .45, .5],
                                  [.3, .2, .7, .8]]], onp.float32),
                    onp.zeros(4, onp.float32), onp.ones(4, onp.float32)],
                   {}),
    "multibox_target": ([_R.rand(1, 4, 4).astype(onp.float32),
                         onp.asarray([[[1, .1, .1, .6, .6]]], onp.float32),
                         onp.zeros((1, 3, 4), onp.float32)], {}),
    "multibox_detection": ([
        _R.rand(1, 3, 4).astype(onp.float32),
        (_R.rand(1, 16) * 0.1).astype(onp.float32),
        _R.rand(1, 4, 4).astype(onp.float32)], {}),
    "count_sketch": ([_f(2, 6), _i(4, 6).astype(onp.float32),
                      onp.sign(_R.randn(6)).astype(onp.float32)],
                     dict(out_dim=4)),
    # --- optimizer multi-tensor ----------------------------------------
    "adadelta_update": ([_f(4), _f(4), onp.zeros(4, onp.float32),
                         onp.zeros(4, onp.float32)], {}),
    "adamw_update": ([_f(4), _f(4), _f(4), _f(4)], {}),
    "ftrl_update": ([_f(4), _f(4), _f(4), _f(4)], {}),
    # state arrays start at zero (E[g^2] >= E[g]^2 must hold)
    "rmspropalex_update": ([_f(4), _f(4), onp.zeros(4, onp.float32),
                            onp.zeros(4, onp.float32),
                            onp.zeros(4, onp.float32)], {}),
    "lamb_update_phase2": ([_f(4), _f(4), onp.asarray(1.0, onp.float32),
                            onp.asarray(1.0, onp.float32)], {}),
    # interleaved per-weight layout (w0, g0, [aux0...,] w1, g1, ...) —
    # reference optimizer_op.cc:321 FListInputNames
    "multi_sgd_update": ([_f(4), _f(4), _f(3), _f(3)],
                         dict(lrs=(0.1, 0.1), wds=(0.0, 0.0),
                              num_weights=2)),
    "multi_sgd_mom_update": ([_f(4), _f(4), _f(4), _f(3), _f(3), _f(3)],
                             dict(lrs=(0.1, 0.1), wds=(0.0, 0.0),
                                  num_weights=2)),
    "multi_lamb_update": ([_f(4), _f(4), _f(4), _f(4),
                           _f(3), _f(3), _f(3), _f(3)],
                          dict(learning_rates=(0.1, 0.1), wds=(0.0, 0.0),
                               num_tensors=2)),
    "multi_lans_update": ([_f(4), _f(4), _f(4), _f(4),
                           _f(3), _f(3), _f(3), _f(3)],
                          dict(learning_rates=(0.1, 0.1), wds=(0.0, 0.0),
                               num_tensors=2)),
    # --- misc -----------------------------------------------------------
    "softmax_cross_entropy": ([_f(4, 6), _i(6, 4).astype(onp.float32)],
                              {}),
    "sparse_softmax_cross_entropy": (
        [_f(4, 6), _i(6, 4).astype(onp.float32)], {}),
    "multi_token_cross_entropy": (
        [_f(3, 2, 5, 6), _i(6, 3, 5).astype(onp.float32)],
        dict(depth_weights=(1.0, 0.3))),
    "embedding": ([_i(10, 4), _f(10, 8)], {}),
    "take": ([_f(10, 8), _i(10, 4).astype(onp.float32)], {}),
    "Cast": ([_f(4, 6)], dict(dtype="float16")),
    "cast": ([_f(4, 6)], dict(dtype="float16")),
    "arange_like": ([_f(4, 6)], dict(axis=1)),
    "where": ([(_R.rand(4, 6) > 0.5).astype(onp.float32), _f(4, 6),
               _f(4, 6)], {}),
    # --- int8 quantization ops (contrib.quantization) -------------------
    "quantize": ([_f(4, 6)], dict(min_range=-1.0, max_range=1.0)),
    "dequantize": ([(_R.randint(-127, 127, (4, 6))).astype(onp.int8),
                    onp.asarray(-1.0, onp.float32),
                    onp.asarray(1.0, onp.float32)], {}),
    "requantize": ([_R.randint(-4000, 4000, (4, 6)).astype(onp.int32),
                    onp.asarray(-2.0, onp.float32),
                    onp.asarray(2.0, onp.float32)],
                   dict(min_calib_range=-1.0, max_calib_range=1.0)),
    "quantized_conv": ([_R.randint(-127, 127, (1, 3, 6, 6)).astype(
        onp.int8), _R.randint(-127, 127, (4, 3, 3, 3)).astype(onp.int8)],
        dict(kernel=(3, 3), num_filter=4, no_bias=True,
             data_scale=0.01, w_scale=0.01)),
    "quantized_fully_connected": ([
        _R.randint(-127, 127, (4, 6)).astype(onp.int8),
        _R.randint(-127, 127, (8, 6)).astype(onp.int8), _f(8)],
        dict(num_hidden=8, data_scale=0.01, w_scale=0.01)),
    # --- domain-restricted unary ---------------------------------------
    "arcsin": ([(_R.rand(4, 6) * 1.6 - 0.8).astype(onp.float32)], {}),
    "arccos": ([(_R.rand(4, 6) * 1.6 - 0.8).astype(onp.float32)], {}),
    "arctanh": ([(_R.rand(4, 6) * 1.6 - 0.8).astype(onp.float32)], {}),
    "erfinv": ([(_R.rand(4, 6) * 1.6 - 0.8).astype(onp.float32)], {}),
    "arccosh": ([(_R.rand(4, 6) + 1.1).astype(onp.float32)], {}),
    # --- scalar-attr binary ---------------------------------------------
    "div_scalar": ([_f(4, 6)], dict(scalar=2.0)),
    "mod_scalar": ([_f(4, 6)], dict(scalar=2.0)),
    # --- pdf params in-domain -------------------------------------------
    "pdf_negative_binomial": ([_i(5, 4).astype(onp.float32) * 1.0,
                               _f(4) + 1.0,
                               (_R.rand(4) * 0.6 + 0.2).astype(
                                   onp.float32)], {}),
    # --- nn_extra -------------------------------------------------------
    "SyncBatchNorm": ([_f(2, 4, 6, 6), _f(4), _f(4), _f(4), _f(4) + 0.5],
                      {}),
    "BatchNormWithReLU": ([_f(2, 4, 6, 6), _f(4), _f(4), _f(4),
                           _f(4) + 0.5], {}),
    "ROIPooling": ([_f(2, 3, 8, 8),
                    onp.array([[0, 1, 1, 6, 6], [1, 0, 0, 7, 5]],
                              onp.float32)],
                   dict(pooled_size=(2, 2), spatial_scale=1.0)),
    "im2col": ([_f(2, 3, 8, 8)], dict(kernel=(3, 3))),
    "col2im": ([_f(2, 27, 36)],
               dict(output_size=(8, 8), kernel=(3, 3))),
    # --- misc -----------------------------------------------------------
    "Custom": ([_f(4, 6)], dict(op_type="relu")),
    "histogram": ([_f(100).ravel(),
                   onp.linspace(0.0, 1.2, 11).astype(onp.float32)], {}),
    "scatter_set_nd": ([_f(4, 6),
                        onp.stack([_i(4, 5), _i(6, 5)]).astype(onp.int32),
                        _f(5)], {}),
    "dynamic_reshape": ([_f(4, 6), onp.array([6, 4], onp.int32)], {}),
    "hawkesll": ([_f(2, 3) + 0.5,                       # lda (N,K)
                  (_R.rand(3) * 0.5).astype(onp.float32),   # alpha (K,)
                  _f(3) + 0.5,                          # beta (K,)
                  _f(2, 3) * 0.1,                       # state (N,K)
                  _f(2, 5),                             # lags (N,T)
                  _i(3, 2, 5),                          # marks (N,T)
                  onp.array([3, 5], onp.float32),       # valid_length
                  onp.array([20.0, 20.0], onp.float32)],  # max_time
                 {}),
    # --- optimizer variants --------------------------------------------
    "group_adagrad_update": ([_f(4, 6), _f(4, 6), _f(4)], {}),
    "mp_lamb_update_phase2": ([_f(4, 6), _f(4, 6),
                               onp.float32(1.0).reshape(()),
                               onp.float32(1.0).reshape(()),
                               _f(4, 6)], {}),
    "linalg_syevd": ([_psd(5)], {}),
    # --- device image ops ----------------------------------------------
    "to_tensor": ([(_R.rand(8, 8, 3) * 255).astype(onp.float32)], {}),
    "image_resize": ([(_R.rand(8, 8, 3) * 255).astype(onp.float32)],
                     dict(size=(4, 4))),
    "image_crop": ([(_R.rand(8, 8, 3)).astype(onp.float32)],
                   dict(x=1, y=2, width=4, height=3)),
    "image_random_crop": ([(_R.rand(8, 8, 3)).astype(onp.float32),
                           onp.array([1, 2], onp.uint32)],
                          dict(width=4, height=4)),
    "image_random_resized_crop": ([(_R.rand(8, 8, 3)).astype(onp.float32),
                                   onp.array([3, 4], onp.uint32)],
                                  dict(width=4, height=4)),
    "mrcnn_mask_target": ([
        onp.array([[[1, 1, 7, 7], [2, 2, 6, 6]]], onp.float32),   # rois
        _R.rand(1, 3, 10, 10).astype(onp.float32),                # gt_masks
        onp.array([[0, 2]], onp.int32),                           # matches
        onp.array([[1, 2]], onp.int32)],                          # classes
        dict(num_rois=2, num_classes=3, mask_size=(4, 4))),
    # --- rroi / graph / sparse -----------------------------------------
    "RROIAlign": ([_f(2, 3, 12, 12),
                   onp.array([[0, 6, 6, 6, 4, 30.0],
                              [1, 5, 5, 4, 4, -15.0]], onp.float32)],
                  dict(pooled_size=(2, 2))),
    "edge_id": ([onp.array([[0, 1, 0], [2, 0, 3], [0, 0, 0]], onp.float32),
                 _i(3, 4), _i(3, 4)], {}),
    "sparse_retain": ([_f(5, 4), onp.array([0, 3], onp.int32)], {}),
    # --- adamw variants -------------------------------------------------
    "mp_adamw_update": ([_f(4, 6), _f(4, 6), _f(4, 6), _f(4, 6) + 0.1,
                         _f(4, 6)], {}),
    "multi_mp_sgd_update": ([_f(4), _f(4), _f(4), _f(3), _f(3), _f(3)],
                            dict(lrs=(0.1, 0.1), wds=(0.0, 0.0),
                                 num_weights=2)),
    "multi_mp_sgd_mom_update": ([_f(4), _f(4), _f(4), _f(4),
                                 _f(3), _f(3), _f(3), _f(3)],
                                dict(lrs=(0.1, 0.1), wds=(0.0, 0.0),
                                     num_weights=2)),
    # preloaded variants take lrs/wds as trailing DEVICE arrays
    "preloaded_multi_sgd_update": ([_f(4), _f(4), _f(3), _f(3),
                                    onp.full(2, 0.1, onp.float32),
                                    onp.zeros(2, onp.float32)],
                                   dict(num_weights=2)),
    "preloaded_multi_sgd_mom_update": ([_f(4), _f(4), _f(4),
                                        _f(3), _f(3), _f(3),
                                        onp.full(2, 0.1, onp.float32),
                                        onp.zeros(2, onp.float32)],
                                       dict(num_weights=2)),
    "preloaded_multi_mp_sgd_update": ([_f(4), _f(4), _f(4),
                                       _f(3), _f(3), _f(3),
                                       onp.full(2, 0.1, onp.float32),
                                       onp.zeros(2, onp.float32)],
                                      dict(num_weights=2)),
    "preloaded_multi_mp_sgd_mom_update": ([_f(4), _f(4), _f(4), _f(4),
                                           _f(3), _f(3), _f(3), _f(3),
                                           onp.full(2, 0.1, onp.float32),
                                           onp.zeros(2, onp.float32)],
                                          dict(num_weights=2)),
    # interleaved: (w0, g0, m0, v0, [w32_0,] w1, ...) per reference
    # adamw.cc:177 / multi_lamb.cc:186
    "multi_adamw_update": ([_f(3), _f(3), _f(3), _f(3) + 0.1,
                            _f(3), _f(3), _f(3), _f(3) + 0.1],
                           dict(num_weights=2, lrs=(0.1, 0.1),
                                wds=(0.0, 0.0))),
    "multi_mp_adamw_update": ([_f(3), _f(3), _f(3), _f(3) + 0.1, _f(3),
                               _f(3), _f(3), _f(3), _f(3) + 0.1, _f(3)],
                              dict(num_weights=2, lrs=(0.1, 0.1),
                                   wds=(0.0, 0.0))),
    "multi_mp_lamb_update": ([_f(3), _f(3), _f(3), _f(3) + 0.1, _f(3),
                              _f(3), _f(3), _f(3), _f(3) + 0.1, _f(3)],
                             dict(num_tensors=2,
                                  learning_rates=(0.1, 0.1),
                                  wds=(0.0, 0.0), step_count=(1, 1))),
    "multi_mp_lans_update": ([_f(3), _f(3), _f(3), _f(3) + 0.1, _f(3),
                              _f(3), _f(3), _f(3), _f(3) + 0.1, _f(3)],
                             dict(num_tensors=2,
                                  learning_rates=(0.1, 0.1),
                                  wds=(0.0, 0.0), step_count=(1, 1))),
    # --- quantized breadth ---------------------------------------------
    "calibrate_entropy": ([(_R.rand(512) * 100).astype(onp.float32)], {}),
    "quantized_pooling": ([_R.randint(-127, 127, (2, 3, 8, 8)).astype(
        onp.int8), onp.float32(-1.0).reshape(()),
        onp.float32(1.0).reshape(())], dict(kernel=(2, 2))),
    "quantized_batch_norm": ([_R.randint(-127, 127, (2, 4, 6, 6)).astype(
        onp.int8), _f(4), _f(4), _f(4), _f(4) + 0.5,
        onp.float32(-1.0).reshape(()), onp.float32(1.0).reshape(())],
        dict(min_calib_range=-2.0, max_calib_range=2.0)),
    "quantized_concat": ([_R.randint(-127, 127, (2, 3)).astype(onp.int8),
                          _R.randint(-127, 127, (2, 3)).astype(onp.int8),
                          onp.float32(-1.0).reshape(()),
                          onp.float32(1.0).reshape(()),
                          onp.float32(-2.0).reshape(()),
                          onp.float32(2.0).reshape(())],
                         dict(num_args=2)),
    # --- dgl graph sampling (ops/graph_sampling.py) ---------------------
    "dgl_csr_neighbor_uniform_sample": (
        [(_R.rand(5, 5) > 0.5).astype(onp.float32) * 7,
         onp.array([0, 1], onp.int64)],
        dict(num_hops=1, num_neighbor=2, max_num_vertices=5)),
    "dgl_csr_neighbor_non_uniform_sample": (
        [(_R.rand(5, 5) > 0.5).astype(onp.float32) * 7,
         _R.rand(5).astype(onp.float32) + 0.1,
         onp.array([0, 1], onp.int64)],
        dict(num_hops=1, num_neighbor=2, max_num_vertices=5)),
    "dgl_subgraph": ([(_R.rand(5, 5) > 0.5).astype(onp.float32) * 3,
                      onp.array([0, 2, 3], onp.int64)],
                     dict(return_mapping=True)),
    "dgl_adjacency": ([(_R.rand(4, 4) > 0.5).astype(onp.float32) * 5], {}),
    "dgl_graph_compact": ([(_R.rand(5, 5) > 0.6).astype(onp.float32) * 3,
                           onp.array([0, 1, 2, 0, 0, 3], onp.int64)],
                          dict(graph_sizes=(3,))),
    # --- np-surface registration breadth (ops/np_extra.py) -------------
    "bincount": ([_R.randint(0, 5, (12,)).astype(onp.int32)],
                 dict(minlength=6)),
    "cross": ([_f(4, 3), _f(4, 3)], {}),
    "diag_indices_from": ([_f(4, 4)], {}),
    "dsplit": ([_f(2, 4, 2)], dict(indices_or_sections=2)),
    "einsum": ([_f(3, 4), _f(4, 5)], dict(subscripts="ij,jk->ik")),
    "fmod_scalar": ([_f(4, 6) + 1.0], dict(scalar=2.0)),
    "rfmod_scalar": ([_f(4, 6) + 1.0], dict(scalar=2.0)),
    "index_add": ([_f(4, 6), onp.array([[0, 2, 3]], onp.int32), _f(3, 6)],
                  {}),
    "index_update": ([_f(4, 6), onp.array([[1, 3]], onp.int32), _f(2, 6)],
                     {}),
    "insert": ([_f(6)], dict(obj=2, val=1.5)),
    "interp": ([_f(5) * 4, onp.arange(6, dtype=onp.float32),
                _f(6)], {}),
    "linalg_eig": ([_f(4, 4) + 2 * onp.eye(4, dtype=onp.float32)], {}),
    "linalg_eigvals": ([_f(4, 4) + 2 * onp.eye(4, dtype=onp.float32)], {}),
    "linalg_tensorsolve": ([_f(3, 3) + 2 * onp.eye(3, dtype=onp.float32),
                            _f(3)], {}),
}
