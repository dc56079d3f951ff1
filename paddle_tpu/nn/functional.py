"""paddle_tpu.nn.functional — functional NN API surface.

Mirrors paddle.nn.functional by re-exporting the op library
(reference: python/paddle/fluid/layers/nn.py + loss.py functional surface).
"""
from ..ops.nn_ops import *  # noqa: F401,F403
from ..ops.loss import *  # noqa: F401,F403
from ..ops.ssm import *  # noqa: F401,F403
from ..ops.moe import *  # noqa: F401,F403
from ..ops.sparse_attention import *  # noqa: F401,F403
from ..ops.manip import one_hot, pad  # noqa: F401

# --- paddle.nn.functional 1.x surface (reference: python/paddle/nn/
# functional/*.py re-exported the fluid.layers twins under paddle.nn.
# functional; same here, so `from paddle.nn import functional as F`
# code ports verbatim) ---------------------------------------------------
from ..fluid.layers import (  # noqa: F401,E402
    # activation.py
    brelu, hsigmoid, soft_relu,
    # common.py / conv.py
    pad2d, conv3d_transpose, assign,
    # extension.py
    add_position_encoding, multiclass_nms, row_conv, target_assign,
    temporal_shift,
    # learning_rate.py
    cosine_decay, exponential_decay, inverse_time_decay,
    natural_exp_decay, noam_decay, piecewise_decay, polynomial_decay,
    linear_lr_warmup,
    # lod.py
    hash,
    # loss.py
    center_loss, dice_loss, iou_similarity, kldiv_loss, npair_loss,
    sigmoid_focal_loss, smooth_l1, ssd_loss,
    teacher_student_sigmoid_loss,
    # norm.py / pooling.py
    l2_normalize, lrn, pool3d, adaptive_pool2d, adaptive_pool3d,
    # vision.py
    affine_channel, affine_grid, anchor_generator, bipartite_match,
    box_clip, box_coder, box_decoder_and_assign, collect_fpn_proposals,
    deformable_roi_pooling, density_prior_box, detection_output,
    distribute_fpn_proposals, generate_mask_labels,
    generate_proposal_labels, generate_proposals, grid_sampler,
    image_resize, prior_box, prroi_pool, psroi_pool, resize_bilinear,
    resize_nearest, resize_trilinear, roi_align, roi_pool,
    space_to_depth, yolo_box, yolov3_loss,
)
from .functional_aliases import (  # noqa: F401,E402
    logsigmoid, tanh_shrink, diag_embed)
