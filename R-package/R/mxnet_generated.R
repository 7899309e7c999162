# Generated by tools/gen_r_ops.py from the live operator registry —
# DO NOT EDIT BY HAND; regenerate after registering new operators.
# Reference analogue: R-package/R/mxnet_generated.R.
#
# Each mx.symbol.<Op> accepts symbol inputs and string-typed params as
# named arguments plus an optional name=; each mx.nd.<fn> takes NDArray
# inputs and numeric scalars positionally.  Both route through the same
# internal constructors the dynamic load-time export uses.

# ---- symbol operators (registry) ----

#' reference activation-inl.h:182.
#' @param act_type act_type parameter (required; one of relu, sigmoid, tanh, softrelu, silu, relu2)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Activation <- function(...) {
  mx.symbol.internal.create('Activation', list(...))
}

#' reference batch_norm-inl.h:305 (eps=1e-3, momentum=0.9, fix_gamma=True).
#' @param eps eps parameter (default 0.001)
#' @param momentum momentum parameter (default 0.9)
#' @param fix_gamma fix_gamma parameter (default True)
#' @param use_global_stats use_global_stats parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.BatchNorm <- function(...) {
  mx.symbol.internal.create('BatchNorm', list(...))
}

#' reference block_grad-inl.h: identity forward, zero gradient.
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.BlockGrad <- function(...) {
  mx.symbol.internal.create('BlockGrad', list(...))
}

#' reference cast-inl.h.
#' @param dtype dtype parameter (required; one of float16, float32, float64, bfloat16, uint8, int32, int64)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Cast <- function(...) {
  mx.symbol.internal.create('Cast', list(...))
}

#' Depthwise causal convolution over time of ``(B, T, C)``: a ``kernel``-tap filter a channel
#' @param kernel kernel parameter (default 4)
#' @param lanes lanes parameter
#' @param act_type act_type parameter (one of relu, sigmoid, tanh, softrelu, silu, relu2)
#' @param gated gated parameter
#' @param no_bias no_bias parameter
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.CausalConv1D <- function(...) {
  mx.symbol.internal.create('CausalConv1D', list(...))
}

#' Masked multi-head self-attention over ``(B, T, H, Dh)`` query,
#' @param scale scale parameter (default 0.0)
#' @param layer layer parameter (default -1)
#' @param mask mask parameter (default causal; one of causal, block_diffusion, sliding_window)
#' @param block block parameter (default 0)
#' @param window window parameter (default 0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.CausalSelfAttention <- function(...) {
  mx.symbol.internal.create('CausalSelfAttention', list(...))
}

#' reference concat-inl.h (num_args variable inputs, dim param).
#' @param num_args num_args parameter (required)
#' @param dim dim parameter (default 1)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Concat <- function(...) {
  mx.symbol.internal.create('Concat', list(...))
}

#' reference convolution-inl.h:483 (im2col+gemm -> MXU conv).
#' @param kernel kernel parameter (required)
#' @param stride stride parameter (default (1, 1))
#' @param dilate dilate parameter (default (1, 1))
#' @param pad pad parameter (default (0, 0))
#' @param num_filter num_filter parameter (required)
#' @param num_group num_group parameter (default 1)
#' @param workspace workspace parameter (default 512)
#' @param no_bias no_bias parameter (default False)
#' @param cudnn_tune cudnn_tune parameter
#' @param cudnn_off cudnn_off parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Convolution <- function(...) {
  mx.symbol.internal.create('Convolution', list(...))
}

#' reference correlation.cu (FlowNet correlation layer).
#' @param kernel_size kernel_size parameter (default 1)
#' @param max_displacement max_displacement parameter (default 1)
#' @param stride1 stride1 parameter (default 1)
#' @param stride2 stride2 parameter (default 1)
#' @param pad_size pad_size parameter (default 0)
#' @param is_multiply is_multiply parameter (default True)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Correlation <- function(...) {
  mx.symbol.internal.create('Correlation', list(...))
}

#' reference crop-inl.h: crop x to h_w (or to shape of second input).
#' @param num_args num_args parameter (default 1)
#' @param offset offset parameter (default (0, 0))
#' @param h_w h_w parameter (default (0, 0))
#' @param center_crop center_crop parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Crop <- function(...) {
  mx.symbol.internal.create('Crop', list(...))
}

#' reference cudnn_batch_norm-inl.h — same semantics; on TPU the XLA
#' @param eps eps parameter (default 0.001)
#' @param momentum momentum parameter (default 0.9)
#' @param fix_gamma fix_gamma parameter (default True)
#' @param use_global_stats use_global_stats parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.CuDNNBatchNorm <- function(...) {
  mx.symbol.internal.create('CuDNNBatchNorm', list(...))
}

#' sym.Custom(..., op_type=\'name\') (reference custom-inl.h:211).
#' @param op_type op_type parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Custom <- function(...) {
  mx.symbol.internal.create('Custom', list(...))
}

#' reference deconvolution-inl.h: out = s·(x-1) + k - 2p + adj.
#' @param kernel kernel parameter (required)
#' @param stride stride parameter (default (1, 1))
#' @param pad pad parameter (default (0, 0))
#' @param adj adj parameter (default (0, 0))
#' @param target_shape target_shape parameter (default (0, 0))
#' @param num_filter num_filter parameter (required)
#' @param num_group num_group parameter (default 1)
#' @param workspace workspace parameter (default 512)
#' @param no_bias no_bias parameter (default True)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Deconvolution <- function(...) {
  mx.symbol.internal.create('Deconvolution', list(...))
}

#' reference dropout-inl.h (scale by 1/(1-p) at train time).
#' @param p p parameter (default 0.5)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Dropout <- function(...) {
  mx.symbol.internal.create('Dropout', list(...))
}

#' reference elementwise_sum-inl.h.
#' @param num_args num_args parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.ElementWiseSum <- function(...) {
  mx.symbol.internal.create('ElementWiseSum', list(...))
}

#' reference embedding-inl.h: weight[(int)data].
#' @param input_dim input_dim parameter (required)
#' @param output_dim output_dim parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Embedding <- function(...) {
  mx.symbol.internal.create('Embedding', list(...))
}

#' reference reshape-inl.h FlattenOp: (N, ...) -> (N, prod).
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Flatten <- function(...) {
  mx.symbol.internal.create('Flatten', list(...))
}

#' reference fully_connected-inl.h:242.  y = x·Wᵀ + b, x flattened to 2D.
#' @param num_hidden num_hidden parameter (required)
#' @param no_bias no_bias parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.FullyConnected <- function(...) {
  mx.symbol.internal.create('FullyConnected', list(...))
}

#' Gated DeltaNet (arXiv:2412.06464) over ``(B, T, Hk, Dk)`` query
#' @param layer layer parameter (default -1)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.GatedDeltaNet <- function(...) {
  mx.symbol.internal.create('GatedDeltaNet', list(...))
}

#' ``RMSNorm`` over every ``head_dim`` lanes of ``(..., H * head_dim)``
#' @param head_dim head_dim parameter (required)
#' @param eps eps parameter (default 1e-05)
#' @param act_type act_type parameter (default silu; one of silu, sigmoid)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.GatedRMSNorm <- function(...) {
  mx.symbol.internal.create('GatedRMSNorm', list(...))
}

#' Between a q or k projection and attention, on the ``(B * T, heads
#' @param head_dim head_dim parameter (required)
#' @param norm norm parameter (default True)
#' @param eps eps parameter (default 1e-06)
#' @param seq_len seq_len parameter (default 0)
#' @param theta theta parameter (default 10000.0)
#' @param period period parameter (default 0)
#' @param sections sections parameter
#' @param with_positions with_positions parameter
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.HeadNormRotary <- function(...) {
  mx.symbol.internal.create('HeadNormRotary', list(...))
}

#' reference identity_attach_KL_sparse_reg-inl.h: identity forward with a
#' @param sparseness_target sparseness_target parameter (default 0.1)
#' @param penalty penalty parameter (default 0.001)
#' @param momentum momentum parameter (default 0.9)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.IdentityAttachKLSparseReg <- function(...) {
  mx.symbol.internal.create('IdentityAttachKLSparseReg', list(...))
}

#' Causal self-attention over the keys a learned indexer selects
#' @param topk topk parameter (required)
#' @param scale scale parameter (default 0.0)
#' @param layer layer parameter (default -1)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.IndexedSelfAttention <- function(...) {
  mx.symbol.internal.create('IndexedSelfAttention', list(...))
}

#' Kimi Delta Attention over ``(B, T, H, Dk)`` query and key, ``(B,
#' @param layer layer parameter (default -1)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.KimiDeltaAttention <- function(...) {
  mx.symbol.internal.create('KimiDeltaAttention', list(...))
}

#' reference l2_normalization-inl.h: per-instance L2 normalize.
#' @param eps eps parameter (default 1e-10)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.L2Normalization <- function(...) {
  mx.symbol.internal.create('L2Normalization', list(...))
}

#' reference lrn-inl.h: cross-channel, alpha/nsize scaling.
#' @param alpha alpha parameter (default 0.0001)
#' @param beta beta parameter (default 0.75)
#' @param knorm knorm parameter (default 2.0)
#' @param nsize nsize parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.LRN <- function(...) {
  mx.symbol.internal.create('LRN', list(...))
}

#' Layer normalization over the last axis (Ba et al. 2016): ``(x -
#' @param eps eps parameter (default 1e-05)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.LayerNorm <- function(...) {
  mx.symbol.internal.create('LayerNorm', list(...))
}

#' reference leaky_relu-inl.h:328 (leaky/prelu/rrelu/elu).
#' @param act_type act_type parameter (default leaky; one of leaky, prelu, rrelu, elu)
#' @param slope slope parameter (default 0.25)
#' @param lower_bound lower_bound parameter (default 0.125)
#' @param upper_bound upper_bound parameter (default 0.334)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.LeakyReLU <- function(...) {
  mx.symbol.internal.create('LeakyReLU', list(...))
}

#' reference regression_output-inl.h (identity fwd, out-label bwd).
#' @param grad_scale grad_scale parameter (default 1.0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.LinearRegressionOutput <- function(...) {
  mx.symbol.internal.create('LinearRegressionOutput', list(...))
}

#' reference regression_output-inl.h (sigmoid fwd, out-label bwd).
#' @param grad_scale grad_scale parameter (default 1.0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.LogisticRegressionOutput <- function(...) {
  mx.symbol.internal.create('LogisticRegressionOutput', list(...))
}

#' reference regression_output-inl.h (identity fwd, sign(out-label) bwd).
#' @param grad_scale grad_scale parameter (default 1.0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.MAERegressionOutput <- function(...) {
  mx.symbol.internal.create('MAERegressionOutput', list(...))
}

#' reference make_loss-inl.h: forward identity; backward injects
#' @param grad_scale grad_scale parameter (default 1.0)
#' @param normalization normalization parameter (default null; one of null, batch, valid)
#' @param valid_thresh valid_thresh parameter (default 0.0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.MakeLoss <- function(...) {
  mx.symbol.internal.create('MakeLoss', list(...))
}

#' reference pooling-inl.h (floor convention, line 197).
#' @param kernel kernel parameter (required)
#' @param pool_type pool_type parameter (default max; one of max, avg, sum)
#' @param global_pool global_pool parameter (default False)
#' @param stride stride parameter (default (1, 1))
#' @param pad pad parameter (default (0, 0))
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Pooling <- function(...) {
  mx.symbol.internal.create('Pooling', list(...))
}

#' Root-mean-square LayerNorm over the last axis (Zhang & Sennrich
#' @param eps eps parameter (default 1e-05)
#' @param groups groups parameter
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.RMSNorm <- function(...) {
  mx.symbol.internal.create('RMSNorm', list(...))
}

#' Multi-layer unidirectional recurrent block over lax.scan.
#' @param state_size state_size parameter (required)
#' @param num_layers num_layers parameter (required)
#' @param mode mode parameter (required; one of rnn_relu, rnn_tanh, gru, lstm)
#' @param p p parameter (default 0.0)
#' @param state_outputs state_outputs parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.RNN <- function(...) {
  mx.symbol.internal.create('RNN', list(...))
}

#' reference roi_pooling.cc: max-pool each ROI into a fixed grid.
#' @param pooled_size pooled_size parameter (required)
#' @param spatial_scale spatial_scale parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.ROIPooling <- function(...) {
  mx.symbol.internal.create('ROIPooling', list(...))
}

#' Apply a body Symbol ``num_steps`` times over one set of inputs;
#' @param body body parameter (required)
#' @param num_steps num_steps parameter (required)
#' @param carry carry parameter (required)
#' @param recompute recompute parameter (default True)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Repeat <- function(...) {
  mx.symbol.internal.create('Repeat', list(...))
}

#' reference reshape-inl.h:370 (supports 0 = copy dim, -1 = infer).
#' @param target_shape target_shape parameter
#' @param shape shape parameter
#' @param keep_highest keep_highest parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Reshape <- function(...) {
  mx.symbol.internal.create('Reshape', list(...))
}

#' Rotary position embedding of ``(B, T, H, Dh)`` (Su et al. 2021),
#' @param theta theta parameter (default 10000.0)
#' @param period period parameter (default 0)
#' @param sections sections parameter
#' @param with_positions with_positions parameter
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.RotaryEmbedding <- function(...) {
  mx.symbol.internal.create('RotaryEmbedding', list(...))
}

#' The state-space scan of Mamba-2 over ``(B, T, H, P)`` data, ``(B,
#' @param layer layer parameter (default -1)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SSDScan <- function(...) {
  mx.symbol.internal.create('SSDScan', list(...))
}

#' reference svm_output-inl.h: hinge-loss gradient layer.
#' @param margin margin parameter (default 1.0)
#' @param regularization_coefficient regularization_coefficient parameter (default 1.0)
#' @param use_linear use_linear parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SVMOutput <- function(...) {
  mx.symbol.internal.create('SVMOutput', list(...))
}

#' reference slice_channel-inl.h: split along axis into num_outputs.
#' @param num_outputs num_outputs parameter (required)
#' @param axis axis parameter (default 1)
#' @param squeeze_axis squeeze_axis parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SliceChannel <- function(...) {
  mx.symbol.internal.create('SliceChannel', list(...))
}

#' Deprecated alias of SoftmaxOutput (reference softmax_output.cc).
#' @param grad_scale grad_scale parameter (default 1.0)
#' @param ignore_label ignore_label parameter (default -1.0)
#' @param multi_output multi_output parameter (default False)
#' @param use_ignore use_ignore parameter (default False)
#' @param prob_label prob_label parameter (default False)
#' @param normalization normalization parameter (default null; one of null, batch, valid)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.Softmax <- function(...) {
  mx.symbol.internal.create('Softmax', list(...))
}

#' reference softmax_activation-inl.h (mode instance/channel).
#' @param mode mode parameter (default instance; one of instance, channel)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SoftmaxActivation <- function(...) {
  mx.symbol.internal.create('SoftmaxActivation', list(...))
}

#' Per-token softmax cross-entropy that emits the LOSS: logits
#' @param use_ignore use_ignore parameter (default False)
#' @param ignore_label ignore_label parameter (default -1)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SoftmaxCELoss <- function(...) {
  mx.symbol.internal.create('SoftmaxCELoss', list(...))
}

#' reference softmax_output-inl.h:342.
#' @param grad_scale grad_scale parameter (default 1.0)
#' @param ignore_label ignore_label parameter (default -1.0)
#' @param multi_output multi_output parameter (default False)
#' @param use_ignore use_ignore parameter (default False)
#' @param prob_label prob_label parameter (default False)
#' @param normalization normalization parameter (default null; one of null, batch, valid)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SoftmaxOutput <- function(...) {
  mx.symbol.internal.create('SoftmaxOutput', list(...))
}

#' reference spatial_transformer-inl.h: affine grid + bilinear sampler.
#' @param target_shape target_shape parameter (required)
#' @param transform_type transform_type parameter (default affine; one of affine)
#' @param sampler_type sampler_type parameter (default bilinear; one of bilinear)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SpatialTransformer <- function(...) {
  mx.symbol.internal.create('SpatialTransformer', list(...))
}

#' reference swapaxis-inl.h.
#' @param dim1 dim1 parameter (default 0)
#' @param dim2 dim2 parameter (default 0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.SwapAxis <- function(...) {
  mx.symbol.internal.create('SwapAxis', list(...))
}

#' reference upsampling-inl.h (nearest + bilinear-as-deconv).
#' @param scale scale parameter (required)
#' @param num_filter num_filter parameter (default 0)
#' @param sample_type sample_type parameter (required; one of nearest, bilinear)
#' @param multi_input_mode multi_input_mode parameter (default concat; one of concat, sum)
#' @param num_args num_args parameter (default 1)
#' @param workspace workspace parameter (default 512)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.UpSampling <- function(...) {
  mx.symbol.internal.create('UpSampling', list(...))
}

#' WarpCTC operator
#' @param label_length label_length parameter (required)
#' @param input_length input_length parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.WarpCTC <- function(...) {
  mx.symbol.internal.create('WarpCTC', list(...))
}

#' abs operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.abs <- function(...) {
  mx.symbol.internal.create('abs', list(...))
}

#' argmax_channel operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.argmax_channel <- function(...) {
  mx.symbol.internal.create('argmax_channel', list(...))
}

#' batch_dot operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.batch_dot <- function(...) {
  mx.symbol.internal.create('batch_dot', list(...))
}

#' broadcast_axis operator
#' @param axis axis parameter (default ())
#' @param size size parameter (default ())
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.broadcast_axis <- function(...) {
  mx.symbol.internal.create('broadcast_axis', list(...))
}

#' broadcast_div operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.broadcast_div <- function(...) {
  mx.symbol.internal.create('broadcast_div', list(...))
}

#' broadcast_minus operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.broadcast_minus <- function(...) {
  mx.symbol.internal.create('broadcast_minus', list(...))
}

#' broadcast_mul operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.broadcast_mul <- function(...) {
  mx.symbol.internal.create('broadcast_mul', list(...))
}

#' broadcast_plus operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.broadcast_plus <- function(...) {
  mx.symbol.internal.create('broadcast_plus', list(...))
}

#' broadcast_power operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.broadcast_power <- function(...) {
  mx.symbol.internal.create('broadcast_power', list(...))
}

#' broadcast_to operator
#' @param shape shape parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.broadcast_to <- function(...) {
  mx.symbol.internal.create('broadcast_to', list(...))
}

#' ceil operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.ceil <- function(...) {
  mx.symbol.internal.create('ceil', list(...))
}

#' cos operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.cos <- function(...) {
  mx.symbol.internal.create('cos', list(...))
}

#' crop operator
#' @param begin begin parameter (default ())
#' @param end end parameter (default ())
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.crop <- function(...) {
  mx.symbol.internal.create('crop', list(...))
}

#' dot operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.dot <- function(...) {
  mx.symbol.internal.create('dot', list(...))
}

#' exp operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.exp <- function(...) {
  mx.symbol.internal.create('exp', list(...))
}

#' expand_dims operator
#' @param axis axis parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.expand_dims <- function(...) {
  mx.symbol.internal.create('expand_dims', list(...))
}

#' flip operator
#' @param axis axis parameter (required)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.flip <- function(...) {
  mx.symbol.internal.create('flip', list(...))
}

#' floor operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.floor <- function(...) {
  mx.symbol.internal.create('floor', list(...))
}

#' log operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.log <- function(...) {
  mx.symbol.internal.create('log', list(...))
}

#' max operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.max <- function(...) {
  mx.symbol.internal.create('max', list(...))
}

#' max_axis operator
#' @param axis axis parameter (default (0,))
#' @param keepdims keepdims parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.max_axis <- function(...) {
  mx.symbol.internal.create('max_axis', list(...))
}

#' min operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.min <- function(...) {
  mx.symbol.internal.create('min', list(...))
}

#' min_axis operator
#' @param axis axis parameter (default (0,))
#' @param keepdims keepdims parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.min_axis <- function(...) {
  mx.symbol.internal.create('min_axis', list(...))
}

#' norm operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.norm <- function(...) {
  mx.symbol.internal.create('norm', list(...))
}

#' round operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.round <- function(...) {
  mx.symbol.internal.create('round', list(...))
}

#' rsqrt operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.rsqrt <- function(...) {
  mx.symbol.internal.create('rsqrt', list(...))
}

#' sign operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.sign <- function(...) {
  mx.symbol.internal.create('sign', list(...))
}

#' sin operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.sin <- function(...) {
  mx.symbol.internal.create('sin', list(...))
}

#' slice_axis operator
#' @param axis axis parameter (required)
#' @param begin begin parameter (default 0)
#' @param end end parameter (default 0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.slice_axis <- function(...) {
  mx.symbol.internal.create('slice_axis', list(...))
}

#' smooth_l1 operator
#' @param sigma sigma parameter (default 1.0)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.smooth_l1 <- function(...) {
  mx.symbol.internal.create('smooth_l1', list(...))
}

#' softmax_cross_entropy operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.softmax_cross_entropy <- function(...) {
  mx.symbol.internal.create('softmax_cross_entropy', list(...))
}

#' sqrt operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.sqrt <- function(...) {
  mx.symbol.internal.create('sqrt', list(...))
}

#' square operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.square <- function(...) {
  mx.symbol.internal.create('square', list(...))
}

#' sum operator
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.sum <- function(...) {
  mx.symbol.internal.create('sum', list(...))
}

#' sum_axis operator
#' @param axis axis parameter (default (0,))
#' @param keepdims keepdims parameter (default False)
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.sum_axis <- function(...) {
  mx.symbol.internal.create('sum_axis', list(...))
}

#' transpose operator
#' @param axes axes parameter (default ())
#' @param name string, node name (auto when omitted)
#' @return MXSymbol
mx.symbol.transpose <- function(...) {
  mx.symbol.internal.create('transpose', list(...))
}

# ---- imperative ndarray functions ----

#' Imperative Activation: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Activation <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Activation', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative BlockGrad: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.BlockGrad <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('BlockGrad', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Cast: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Cast <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Cast', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative CausalConv1D: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.CausalConv1D <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('CausalConv1D', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative CausalSelfAttention: 3 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.CausalSelfAttention <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('CausalSelfAttention', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Concat: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Concat <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Concat', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Convolution: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Convolution <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Convolution', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Correlation: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Correlation <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Correlation', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Crop: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Crop <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Crop', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Deconvolution: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Deconvolution <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Deconvolution', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Dropout: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.Dropout <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Dropout', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative ElementWiseSum: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.ElementWiseSum <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('ElementWiseSum', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Embedding: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Embedding <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Embedding', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Flatten: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Flatten <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Flatten', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative FullyConnected: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.FullyConnected <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('FullyConnected', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative GatedDeltaNet: 7 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.GatedDeltaNet <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('GatedDeltaNet', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative GatedRMSNorm: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.GatedRMSNorm <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('GatedRMSNorm', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative HeadNormRotary: 1 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.HeadNormRotary <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('HeadNormRotary', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative IndexedSelfAttention: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.IndexedSelfAttention <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('IndexedSelfAttention', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative KimiDeltaAttention: 7 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.KimiDeltaAttention <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('KimiDeltaAttention', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative L2Normalization: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.L2Normalization <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('L2Normalization', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative LRN: 1 NDArray input(s), 3 scalar(s)
#' @return MXNDArray
mx.nd.LRN <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('LRN', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative LayerNorm: 3 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.LayerNorm <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('LayerNorm', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative LeakyReLU: 1 NDArray input(s), 3 scalar(s)
#' @return MXNDArray
mx.nd.LeakyReLU <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('LeakyReLU', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative LinearRegressionOutput: 2 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.LinearRegressionOutput <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('LinearRegressionOutput', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative LogisticRegressionOutput: 2 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.LogisticRegressionOutput <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('LogisticRegressionOutput', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative MAERegressionOutput: 2 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.MAERegressionOutput <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('MAERegressionOutput', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative MakeLoss: 1 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.MakeLoss <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('MakeLoss', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Pooling: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Pooling <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Pooling', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative RMSNorm: 2 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.RMSNorm <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('RMSNorm', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative RNN: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.RNN <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('RNN', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative ROIPooling: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.ROIPooling <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('ROIPooling', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Repeat: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Repeat <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Repeat', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Reshape: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.Reshape <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Reshape', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative RotaryEmbedding: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.RotaryEmbedding <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('RotaryEmbedding', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SSDScan: 7 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.SSDScan <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SSDScan', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SVMOutput: 2 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.SVMOutput <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SVMOutput', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SliceChannel: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.SliceChannel <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SliceChannel', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative Softmax: 2 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.Softmax <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('Softmax', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SoftmaxActivation: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.SoftmaxActivation <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SoftmaxActivation', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SoftmaxCELoss: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.SoftmaxCELoss <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SoftmaxCELoss', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SoftmaxOutput: 2 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.SoftmaxOutput <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SoftmaxOutput', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SpatialTransformer: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.SpatialTransformer <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SpatialTransformer', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative SwapAxis: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.SwapAxis <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('SwapAxis', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative UpSampling: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.UpSampling <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('UpSampling', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _CrossDeviceCopy: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.CrossDeviceCopy <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_CrossDeviceCopy', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _abs: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.abs <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_abs', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _ceil: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.ceil <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_ceil', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _contrib_dequantize: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.contrib_dequantize <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_contrib_dequantize', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _contrib_quantize: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.contrib_quantize <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_contrib_quantize', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _cos: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.cos <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_cos', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _div: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.div <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_div', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _div_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.div_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_div_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _exp: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.exp <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_exp', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _floor: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.floor <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_floor', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _fused_Convolution: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.fused_Convolution <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_fused_Convolution', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _fused_FullyConnected: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.fused_FullyConnected <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_fused_FullyConnected', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _fused_elemwise: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.fused_elemwise <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_fused_elemwise', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _fused_quantized_Convolution: 1 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.fused_quantized_Convolution <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_fused_quantized_Convolution', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _fused_quantized_FullyConnected: 1 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.fused_quantized_FullyConnected <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_fused_quantized_FullyConnected', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _log: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.log <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_log', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _maximum: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.maximum <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_maximum', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _maximum_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.maximum_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_maximum_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _minimum: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.minimum <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_minimum', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _minimum_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.minimum_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_minimum_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _minus: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.minus <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_minus', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _minus_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.minus_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_minus_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _moe_combine: 4 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.moe_combine <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_moe_combine', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _moe_dispatch: 1 NDArray input(s), 3 scalar(s)
#' @return MXNDArray
mx.nd.moe_dispatch <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_moe_dispatch', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _moe_expert_ffn: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.moe_expert_ffn <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_moe_expert_ffn', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _moe_share_ffn: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.moe_share_ffn <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_moe_share_ffn', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _mul: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.mul <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_mul', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _mul_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.mul_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_mul_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _plus: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.plus <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_plus', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _plus_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.plus_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_plus_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _power: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.power <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_power', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _power_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.power_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_power_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _quantized_Convolution: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.quantized_Convolution <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_quantized_Convolution', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _quantized_FullyConnected: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.quantized_FullyConnected <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_quantized_FullyConnected', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _rdiv_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.rdiv_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_rdiv_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _rminus_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.rminus_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_rminus_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _round: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.round <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_round', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _rpower_scalar: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.rpower_scalar <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_rpower_scalar', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _rsqrt: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.rsqrt <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_rsqrt', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _sample_normal: 0 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.sample_normal <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_sample_normal', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _sample_uniform: 0 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.sample_uniform <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_sample_uniform', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _sign: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.sign <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_sign', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _sin: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.sin <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_sin', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _sparse_embedding: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.sparse_embedding <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_sparse_embedding', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _sqrt: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.sqrt <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_sqrt', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative _square: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.square <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('_square', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative argmax_channel: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.argmax_channel <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('argmax_channel', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative batch_dot: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.batch_dot <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('batch_dot', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative broadcast_axis: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.broadcast_axis <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('broadcast_axis', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative broadcast_div: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.broadcast_div <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('broadcast_div', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative broadcast_minus: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.broadcast_minus <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('broadcast_minus', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative broadcast_mul: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.broadcast_mul <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('broadcast_mul', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative broadcast_plus: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.broadcast_plus <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('broadcast_plus', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative broadcast_power: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.broadcast_power <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('broadcast_power', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative broadcast_to: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.broadcast_to <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('broadcast_to', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative choose_element_0index: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.choose_element_0index <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('choose_element_0index', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative clip: 1 NDArray input(s), 2 scalar(s)
#' @return MXNDArray
mx.nd.clip <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('clip', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative crop: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.crop <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('crop', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative dot: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.dot <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('dot', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative expand_dims: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.expand_dims <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('expand_dims', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative flip: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.flip <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('flip', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative max: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.max <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('max', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative max_axis: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.max_axis <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('max_axis', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative min: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.min <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('min', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative min_axis: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.min_axis <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('min_axis', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative norm: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.norm <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('norm', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative onehot_encode: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.onehot_encode <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('onehot_encode', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative slice_axis: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.slice_axis <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('slice_axis', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative smooth_l1: 1 NDArray input(s), 1 scalar(s)
#' @return MXNDArray
mx.nd.smooth_l1 <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('smooth_l1', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative softmax_cross_entropy: 2 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.softmax_cross_entropy <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('softmax_cross_entropy', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative sum: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.sum <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('sum', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative sum_axis: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.sum_axis <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('sum_axis', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

#' Imperative transpose: 1 NDArray input(s), 0 scalar(s)
#' @return MXNDArray
mx.nd.transpose <- function(..., ctx = mx.cpu()) {
  a <- list(...)
  is.nd <- vapply(a, function(x) inherits(x, 'MXNDArray'), logical(1))
  mx.nd.internal.invoke('transpose', a[is.nd], as.double(unlist(a[!is.nd])), ctx)
}

