"""paddle_tpu.nn.functional — functional API surface
(reference: python/paddle/nn/functional/)."""
from .activation import *  # noqa: F401,F403
from .common import (alpha_dropout, bilinear, channel_shuffle,
                     cosine_similarity, dropout, dropout2d, dropout3d,
                     embedding, fold, interpolate, label_smooth, linear,
                     normalize, one_hot, pad, pixel_shuffle, pixel_unshuffle,
                     sequence_mask, unfold, upsample)
from .conv import (conv1d, conv1d_transpose, conv2d, conv2d_transpose, conv3d,
                   conv3d_transpose)
from .norm import (batch_norm, group_norm, instance_norm, layer_norm,
                   local_response_norm, rms_norm, spectral_norm)
from .pooling import (adaptive_avg_pool1d, adaptive_avg_pool2d,
                      adaptive_avg_pool3d, adaptive_max_pool1d,
                      adaptive_max_pool2d, avg_pool1d, avg_pool2d, avg_pool3d,
                      max_pool1d, max_pool2d, max_pool3d)
from .loss import (binary_cross_entropy, binary_cross_entropy_with_logits,
                   cosine_embedding_loss, cross_entropy, ctc_loss,
                   hinge_embedding_loss, kl_div, l1_loss, log_loss,
                   margin_ranking_loss, mse_loss, nll_loss, sigmoid_focal_loss,
                   smooth_l1_loss, softmax_with_cross_entropy,
                   square_error_cost, triplet_margin_loss)
from .attention import (packed_attention, packed_attention_supported,
                        rotary_embedding, scaled_dot_product_attention,
                        sdpa_reference_raw)
