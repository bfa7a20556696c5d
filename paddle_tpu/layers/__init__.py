"""Layer implementations, registered by reference ``LayerConfig.type`` name.

Importing this package registers every layer type (the reference does this
with static ``REGISTER_LAYER`` initializers across ``paddle/gserver/layers``).
"""

from paddle_tpu.layers import activations  # noqa: F401
from paddle_tpu.layers import common  # noqa: F401
from paddle_tpu.layers import conv  # noqa: F401
from paddle_tpu.layers import cost  # noqa: F401
from paddle_tpu.layers import norm  # noqa: F401
from paddle_tpu.layers import pool  # noqa: F401
from paddle_tpu.layers import recurrent  # noqa: F401
from paddle_tpu.layers import sequence  # noqa: F401
from paddle_tpu.layers import group  # noqa: F401
from paddle_tpu.layers import chain  # noqa: F401
from paddle_tpu.layers import misc  # noqa: F401
from paddle_tpu.layers import sampling  # noqa: F401
from paddle_tpu.layers import detection  # noqa: F401
from paddle_tpu.layers import attention  # noqa: F401
from paddle_tpu.layers import moe  # noqa: F401
from paddle_tpu.layers import lm  # noqa: F401
from paddle_tpu.layers import short_conv  # noqa: F401
