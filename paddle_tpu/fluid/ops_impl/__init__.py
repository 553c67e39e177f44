"""Lowering rules for every op type (Fluid op -> pure JAX).

Importing this package registers all rules. Grouped roughly like the
reference's paddle/fluid/operators/ tree, but each op is one JAX rule
instead of a C++ OpKernel pair (CPU/CUDA).
"""
from . import math_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import optim_ops  # noqa: F401
from . import control_ops  # noqa: F401
from . import block_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import crf_ctc_ops  # noqa: F401
from . import sampled_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import linear_attention_ops  # noqa: F401
from . import embedding_ops  # noqa: F401
from . import extra_ops  # noqa: F401
from . import quant_ops  # noqa: F401
