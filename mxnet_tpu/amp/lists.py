"""AMP op lists (reference ``python/mxnet/contrib/amp/lists/symbol_fp16.py``
— the reference classifies its whole operator surface into per-op lists;
this module does the same for this registry, enforced exhaustive by
tests/test_amp_profiler.py).

Four classes, the reference's own:

- LOW_PRECISION_FUNCS (reference FP16_FUNCS): matmul/conv-class ops that
  are safe and fast in bf16/fp16 — these are the MXU ops, where low
  precision doubles throughput.
- FP32_FUNCS: numerically sensitive ops pinned to fp32 — the norms that
  reduce over one sample's features (LayerNorm, GroupNorm, InstanceNorm,
  LRN), softmax / log / exp family, losses, statistics-feeding reductions,
  linear algebra factorizations, probability densities, and optimizer
  update kernels (master-weight math stays fp32).
- WIDEST_TYPE_CASTS: multi-input elementwise ops that follow their widest
  input dtype (reference WIDEST_TYPE_CASTS).
- FP16_FP32_FUNCS: dtype-neutral ops that run correctly in whichever
  precision arrives (moves/reshapes/indexing/comparisons/integer and
  random ops).  The policy leaves their inputs untouched.  BatchNorm is
  here, as in the reference (and as cuDNN's and flax's batch norm under
  mixed precision): it takes the convolution's bf16 and returns bf16,
  with float32 statistics and a float32 multiply-add INSIDE the operator
  (ops/nn.py:batch_norm), so a cast in front of it buys no precision and
  only widens every activation behind it to the next convolution.

On TPU the low-precision dtype is bfloat16 by default — same exponent
range as fp32, so the reference's loss-scaling machinery is optional
(kept for fp16 parity).
"""

LOW_PRECISION_FUNCS = [
    "FullyConnected", "Convolution", "Deconvolution", "dot", "batch_dot",
    "matmul", "interleaved_matmul_selfatt_qk",
    "interleaved_matmul_selfatt_valatt", "interleaved_matmul_encdec_qk",
    "interleaved_matmul_encdec_valatt",
    # the attention core as one op (ops/contrib.py): qkv casts down like
    # the two interleaved ops it replaces in BERT; its softmax statistics
    # are float32 inside, on the Pallas path and the unfused one
    "interleaved_selfatt", "linalg_gemm", "linalg_gemm2",
    "_rnn_fused", "DeformableConvolution", "ModulatedDeformableConvolution",
    "Correlation", "khatri_rao",
]

FP32_FUNCS = [
    # normalization / losses
    "LayerNorm", "GroupNorm", "InstanceNorm", "LRN",
    "L2Normalization", "softmax", "log_softmax", "softmin",
    "softmax_cross_entropy", "SoftmaxOutput", "CTCLoss", "MakeLoss",
    "LinearRegressionOutput", "LogisticRegressionOutput",
    "MAERegressionOutput", "smooth_l1",
    "hawkesll",
    # exp/log family and friends
    "exp", "log", "log2", "log10", "log1p", "expm1", "square", "sqrt",
    "rsqrt", "cbrt", "rcbrt", "power", "power_scalar", "reciprocal",
    "softrelu", "log_sigmoid", "mish", "erf", "erfinv", "gamma",
    "gammaln", "digamma", "hypot", "hypot_scalar", "ldexp", "logaddexp",
    "div_sqrt_dim", "quadratic",
    # statistics-feeding reductions
    "norm", "mean", "sum", "prod", "nansum", "nanprod", "cumsum",
    "cumprod", "moments", "multi_sum_sq", "linalg_sumlogdiag",
    # sensitive inverse-trig / hyperbolic
    "arccos", "arcsin", "arctan", "arccosh", "arcsinh", "arctanh",
    "degrees", "radians",
    # linear-algebra factorizations / solves
    "linalg_cholesky", "linalg_potrf", "linalg_potri", "linalg_det",
    "linalg_slogdet", "linalg_inverse", "linalg_pinv", "linalg_eigh",
    "linalg_eigvalsh", "linalg_svd", "linalg_qr", "linalg_gelqf",
    "linalg_lstsq", "linalg_solve", "linalg_trmm", "linalg_trsm",
    "linalg_syrk", "linalg_tensorinv", "linalg_matrix_rank",
    "linalg_norm_np", "linalg_extractdiag", "linalg_makediag", "linalg_syevd",
    "linalg_maketrian", "linalg_extracttrian",
    # spectral / sketching
    "fft", "ifft", "count_sketch",
    # probability densities
    "pdf_normal", "pdf_uniform", "pdf_gamma", "pdf_exponential",
    "pdf_poisson", "pdf_negative_binomial",
    "pdf_generalized_negative_binomial", "pdf_dirichlet",
    # optimizer update kernels (master weights are fp32)
    "sgd_update", "sgd_mom_update", "nag_mom_update", "adam_update",
    "adamw_update", "adagrad_update", "adadelta_update", "ftrl_update",
    "rmsprop_update", "rmspropalex_update", "signsgd_update",
    "signum_update", "lamb_update_phase1", "lamb_update_phase2",
    "multi_sgd_update", "multi_sgd_mom_update", "multi_lamb_update",
    "multi_lans_update",
    # np-surface additions (ops/np_extra.py): accumulating statistics,
    # exp/log-backed windows+distributions, and linalg stay fp32
    "std", "var", "average", "percentile", "square_sum", "einsum",
    "arctan2", "arctan2_scalar", "rarctan2_scalar", "copysign",
    "copysign_scalar", "rcopysign_scalar", "rpower_scalar",
    "rdiv_scalar", "interp", "polyval", "nan_to_num",
    "linalg_eig", "linalg_eigvals", "linalg_tensorsolve",
    "hanning", "hamming", "blackman", "logspace",
    "laplace", "gumbel", "logistic", "rayleigh", "pareto", "weibull",
    "powerd", "generalized_negative_binomial",
    "SoftmaxActivation",
]

WIDEST_TYPE_CASTS = [
    "broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
    "broadcast_mod", "broadcast_power", "broadcast_maximum",
    "broadcast_minimum", "broadcast_hypot", "add_n", "concat", "stack",
    "where", "elemwise_add", "elemwise_sub", "elemwise_mul",
    "elemwise_div", "amp_multicast",
    "fmax", "fmin", "fmod", "cross", "kron", "tensordot",
    "hstack", "vstack", "dstack", "column_stack",
]

# Everything else: dtype-neutral — runs in whichever precision arrives.
# Kept explicit so the classification is EXHAUSTIVE over the registry
# (tests fail when a new op lands unclassified, mirroring the reference's
# all-ops list files).
FP16_FP32_FUNCS = [
    # batch norm takes and returns the type that arrives (the reference's
    # placement): its statistics and its multiply-add are float32 inside
    # the operator, so a cast in front only widens what reaches HBM
    "BatchNorm", "SyncBatchNorm", "BatchNormWithReLU",
    # the RMS norms likewise: the type that arrives, float32 inside
    "RMSNorm", "GatedRMSNorm",
    # state-space and sparse-expert operators state their own precision:
    # float32 decay sums and carried state in the scan, a float32 router at
    # the highest precision in the expert layer, float32 softmax statistics
    # in the attention core; their products take the activations' type
    # (weights are cast to it inside), so the policy leaves the inputs alone
    "ssd_scan", "causal_conv1d", "causal_gqa_selfatt", "held_experts",
    # rotary angles are float32 inside the operator, as are the latent
    # attention core's rotary key and softmax statistics
    "rope", "causal_latent_selfatt",
    # the gated delta rule likewise: float32 decay sums, triangular inverse
    # and carried state inside, the products in the activations' type; the
    # L2 norm in front of it is float32 inside as the RMS norms are
    "gated_delta_rule", "L2Norm",
    # activations / simple elementwise
    "Activation", "LeakyReLU", "relu", "sigmoid", "tanh", "softsign",
    "hard_sigmoid", "abs", "sign", "negative", "ceil", "floor", "rint",
    "fix", "trunc", "clip", "sin", "cos", "tan", "sinh", "cosh",
    "maximum_scalar", "minimum_scalar", "add_scalar", "sub_scalar",
    "mul_scalar", "div_scalar", "mod_scalar",
    # comparisons / logic (dtype-insensitive outputs)
    "equal_scalar", "not_equal_scalar", "greater_scalar",
    "greater_equal_scalar", "lesser_scalar", "lesser_equal_scalar",
    "broadcast_equal", "broadcast_not_equal", "broadcast_greater",
    "broadcast_greater_equal", "broadcast_lesser",
    "broadcast_lesser_equal", "broadcast_logical_and",
    "broadcast_logical_or", "broadcast_logical_xor", "logical_not",
    "logical_and", "logical_or", "logical_xor", "logical_and_scalar",
    "logical_or_scalar", "logical_xor_scalar", "bitwise_and",
    "bitwise_or", "bitwise_xor", "bitwise_not", "isnan", "isinf",
    "isfinite", "allclose", "all_finite", "multi_all_finite",
    # shape/index/move ops
    "reshape", "Reshape", "npx_reshape", "flatten", "transpose", "expand_dims",
    "squeeze", "swapaxes", "SwapAxis", "slice", "slice_axis",
    "slice_like", "split", "SliceChannel", "take", "batch_take",
    "embedding", "one_hot", "pick", "gather_nd", "scatter_nd",
    "index_copy", "index_array", "boolean_mask", "broadcast_axis",
    "broadcast_to", "repeat", "tile", "reverse", "roll", "rot90", "pad",
    "Pad", "depth_to_space", "space_to_depth", "diag", "triu", "tril",
    "trace", "Crop", "sequence_mask", "sequence_last", "sequence_reverse",
    "sldwin_atten_mask_like", "choose_element_0index",
    "fill_element_0index", "unravel_index", "ravel_multi_index",
    "shape_array", "size_array", "cast", "Cast", "_copy", "_index",
    "BlockGrad", "arange_like",
    # the sparse-label cross-entropy takes its logits as they arrive, like
    # `pick`: its statistics are float32 inside, and in FP32_FUNCS the
    # policy would hand it a float32 copy of the (tokens x vocabulary) array
    "sparse_softmax_cross_entropy", "multi_token_cross_entropy",
    # ordering / extrema (value-preserving)
    "argmax", "argmin", "argmax_channel", "argsort", "sort", "topk",
    "max", "min", "unique",
    # pooling / resampling (window moves, no accumulation hazard in bf16)
    "Pooling", "AdaptiveAvgPooling2D", "UpSampling", "BilinearResize2D",
    "BilinearSampler", "GridGenerator", "SpatialTransformer", "ROIAlign",
    "PSROIPooling", "Dropout",
    # detection (mask/compare logic)
    "box_iou", "box_nms", "box_encode", "box_decode",
    "bipartite_matching", "multibox_prior", "multibox_target",
    "multibox_detection", "Proposal", "mrcnn_mask_target",
    # creation / random (dtype comes from attrs)
    "zeros", "ones", "full", "eye", "arange", "linspace", "zeros_like",
    "ones_like", "normal", "uniform", "randint", "randn", "bernoulli",
    "exponential", "poisson", "negative_binomial", "random_gamma",
    "multinomial", "shuffle",
    # int8 quantization domain (outside amp entirely)
    "quantize", "dequantize", "requantize", "quantized_conv",
    "quantized_fully_connected", "quantize_v2", "quantized_act",
    "quantized_pooling", "quantized_flatten", "quantized_concat",
    "quantized_elemwise_add", "quantized_elemwise_mul",
    "quantized_batch_norm", "quantized_embedding", "calibrate_entropy",
    "intgemm_maxabsolute", "intgemm_prepare_data",
    "intgemm_prepare_weight", "intgemm_take_weight",
    "intgemm_fully_connected",
    # optimizer updates (run in the dtype of their state; mp_* variants
    # own the fp32 master-weight logic internally)
    "ftml_update", "group_adagrad_update", "multi_lars",
    "mp_sgd_update", "mp_sgd_mom_update", "mp_nag_mom_update",
    "mp_lamb_update_phase1", "mp_lamb_update_phase2",
    "multi_mp_sgd_update", "multi_mp_sgd_mom_update",
    "preloaded_multi_sgd_update", "preloaded_multi_sgd_mom_update",
    "preloaded_multi_mp_sgd_update", "preloaded_multi_mp_sgd_mom_update",
    # bookkeeping / data movement (dtype-preserving)
    "amp_cast", "broadcast_like", "reshape_like", "cast_storage",
    "split_v2", "slice_assign", "slice_assign_scalar", "scatter_set_nd",
    "reset_arrays", "histogram", "getnnz", "dynamic_reshape",
    "identity_with_attr_like_rhs", "IdentityAttachKLSparseReg",
    "im2col", "col2im", "ROIPooling", "Custom",
    # device image ops (preprocessing domain)
    "to_tensor", "image_normalize", "image_resize", "image_crop",
    "image_random_crop", "image_random_resized_crop",
    # rroi / graph / sparse
    "RROIAlign", "edge_id", "sparse_retain",
    # adamw/lamb/lans mp+multi variants (fp32 master logic internal)
    "mp_adamw_update", "multi_adamw_update", "multi_mp_adamw_update",
    "multi_mp_lamb_update", "multi_mp_lans_update",
    # np-surface additions (ops/np_extra.py): dtype-preserving
    # manipulation, indexing, integer/bool ops, STE quantization helpers
    "all", "any", "around", "round", "bincount", "diff", "ediff1d",
    "nonzero", "hsplit", "dsplit", "moveaxis", "rollaxis", "diagonal",
    "diagflat", "diag_indices_from", "fill_diagonal", "delete", "insert",
    "atleast_1d", "atleast_2d", "atleast_3d", "share_memory",
    "full_like", "indices", "tri", "tril_indices",
    "lcm", "lcm_scalar", "ldexp_scalar", "rldexp_scalar",
    "fmax_scalar", "fmin_scalar", "fmod_scalar", "rfmod_scalar",
    "rsub_scalar", "rmod_scalar",
    "bitwise_and_scalar", "bitwise_or_scalar", "bitwise_xor_scalar",
    "where_lscalar", "where_rscalar", "where_scalar2",
    "advanced_indexing", "advanced_indexing_multiple",
    "boolean_mask_assign_scalar", "boolean_mask_assign_tensor",
    "index_add", "index_update", "constraint_check", "choice",
    "round_ste", "sign_ste", "gradientmultiplier",
    # dgl graph sampling (host-side minibatch construction)
    "dgl_csr_neighbor_uniform_sample",
    "dgl_csr_neighbor_non_uniform_sample", "dgl_subgraph",
    "dgl_adjacency", "dgl_graph_compact",
]
