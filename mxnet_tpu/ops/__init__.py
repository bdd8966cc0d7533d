"""Operator library (parity: reference src/operator — see SURVEY.md §2.5).

Importing this package registers every operator family into the global registry.
"""
from . import registry
from .registry import OpDef, register, get_op, list_ops, imperative_invoke

# op families — import order is unimportant; each module registers on import
from . import elemwise       # noqa: F401  (elemwise_unary/binary/scalar/broadcast)
from . import init_ops       # noqa: F401  (init_op.cc)
from . import matrix         # noqa: F401  (matrix_op.cc, concat, slice_channel, pad)
from . import reduce_ops     # noqa: F401  (broadcast_reduce_op)
from . import indexing       # noqa: F401  (indexing_op.cc, control_flow_op.cc)
from . import sample_ops     # noqa: F401  (sample_op.cc)
from . import ordering       # noqa: F401  (ordering_op.cc)
from . import nn             # noqa: F401  (conv/pool/bn/act/dropout/...)
from . import loss           # noqa: F401  (softmax_output/regression/make_loss/svm)
from . import optimizer_ops  # noqa: F401  (optimizer_op.cc)
from . import sequence       # noqa: F401  (sequence_*.cc)
from . import rnn_op         # noqa: F401  (rnn.cc / cudnn_rnn-inl.h)
from . import spatial        # noqa: F401  (crop/grid/bilinear/st/roi/correlation)
from . import contrib        # noqa: F401  (multibox_*, proposal, ctc_loss)
from . import custom         # noqa: F401  (Custom — python callback op)
from . import attention      # noqa: F401  (NEW: dot_product_attention/ring,
                             #  LayerNorm — no reference analogue, §5.7)
from . import ssm            # noqa: F401  (NEW: causal_conv1d, ssm_scan)
from . import moe            # noqa: F401  (NEW: moe_router, moe_experts)
from . import kda            # noqa: F401  (NEW: kda_scan)
from . import misc           # noqa: F401  (ndarray-fun registry tail,
                             #  KL sparse reg, v1 aliases)

# ---------------------------------------------------------------- layout pass
# Shape-agnostic ops the executor's NHWC layout pass may flow channel-last
# activations through unchanged (see executor._Lowered.run).  Ops that bake
# in a channel axis (FullyConnected, Flatten, Reshape, SoftmaxOutput, the
# spatial family, ...) stay rigid: the pass restores logical NCHW for them.
_LAYOUT_TRANSPARENT = [
    # unary elementwise
    "relu", "sigmoid", "tanh", "exp", "log", "negative", "abs", "sign",
    "square", "sqrt", "rsqrt", "_copy", "BlockGrad", "Cast", "Dropout",
    "Activation", "clip",
    # binary elementwise (same-shape; residual adds).  elemwise_add etc. are
    # aliases sharing the _plus/_minus/... OpDef objects
    "_plus", "_minus", "_mul", "_div", "_maximum", "_minimum",
    "add_n",
    # scalar variants
    "_plus_scalar", "_minus_scalar", "_rminus_scalar", "_mul_scalar",
    "_div_scalar", "_rdiv_scalar", "_maximum_scalar", "_minimum_scalar",
]
for _name in _LAYOUT_TRANSPARENT:
    # a typo here must fail loudly — a silently-rigid op would make the NHWC
    # pass insert transposes around it, an unmeasured perf regression
    get_op(_name).layout_rule = "transparent"
# LeakyReLU: transparent except prelu (whose gamma broadcasts over axis 1)
get_op("LeakyReLU").layout_rule = (
    lambda attrs: None if attrs.get("act_type") == "prelu" else "transparent")
