"""Operator library: name -> JAX lowering registry.

TPU-native replacement for the reference's nnvm operator registry
(``NNVM_REGISTER_OP`` + FCompute kernels, ``include/mxnet/op_attr_types.h``).
Instead of per-device kernels, each op is a pure JAX function; XLA owns
fusion, tiling and memory planning (what the reference did with
MXPlanMemory / pointwise_fusion_pass / CSE in src/imperative and src/nnvm).
"""
from .registry import OpSchema, register, get_op, find_op, list_ops

from . import tensor  # noqa: F401  (registers ops on import)
from . import elemwise  # noqa: F401
from . import nn  # noqa: F401
from . import reduce as _reduce  # noqa: F401
from . import random as _random  # noqa: F401
from . import init as _init  # noqa: F401
from . import optimizer as _optimizer  # noqa: F401
from . import linalg as _linalg  # noqa: F401
from . import contrib as _contrib  # noqa: F401
from . import detection as _detection  # noqa: F401
from . import extra as _extra  # noqa: F401
from . import control_flow as _control_flow  # noqa: F401
from . import rnn as _rnn  # noqa: F401
from . import nn_extra as _nn_extra  # noqa: F401
from . import misc as _misc  # noqa: F401
from . import image_ops as _image_ops  # noqa: F401
from . import np_extra as _np_extra  # noqa: F401
from . import graph_sampling as _graph_sampling  # noqa: F401
from . import ssm as _ssm  # noqa: F401
from . import rotary as _rotary  # noqa: F401
from . import delta_rule as _delta_rule  # noqa: F401
from . import ref_aliases as _ref_aliases  # noqa: F401  (must be last;
# contrib.quantization registers late — mxnet_tpu/__init__ re-applies)

__all__ = ["OpSchema", "register", "get_op", "find_op", "list_ops"]
