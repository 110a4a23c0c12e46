"""Op kernel registry — importing this package registers every kernel."""
from .registry import register_op, get_op, has_op, registered_ops  # noqa
from . import math_ops      # noqa: F401
from . import nn_ops        # noqa: F401
from . import tensor_ops    # noqa: F401
from . import random_ops    # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import metric_ops    # noqa: F401
from . import crf_ops       # noqa: F401
from . import detection_ops  # noqa: F401
from . import rnn_ops       # noqa: F401
from . import attention_ops  # noqa: F401
from . import ssm_ops       # noqa: F401
from . import moe_ops       # noqa: F401
from . import linear_attn_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import quant_ops     # noqa: F401
from . import vision_ops    # noqa: F401
from . import misc_ops      # noqa: F401
from . import extras_ops    # noqa: F401
from . import loss_extra_ops  # noqa: F401
from . import contrib_ops   # noqa: F401
from . import detection_train_ops  # noqa: F401
