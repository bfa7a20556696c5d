"""Host-side utilities: timers, logging, error context, profiling.

TPU-native successor of ``paddle/utils`` (``Stat.h`` timer registry, glog
``Logging.h``, ``CustomStackTrace`` layer-chain error reporting) — the parts
that stay host-side in a JAX framework. Device-side timing is the jax
profiler (``profiler.py``), because under XLA individual layers fuse and
per-layer host timers would measure nothing.
"""

from paddle_tpu.utils.stat import (Stat, StatRegistry, global_stat, timer,
                                   timer_guard)
from paddle_tpu.utils.log import get_logger, logger
from paddle_tpu.utils.error_context import (current_layer_stack, layer_scope,
                                            LayerStackError)
from paddle_tpu.utils.profiler import StepBreakdown

__all__ = [
    "Stat", "StatRegistry", "global_stat", "timer", "timer_guard",
    "get_logger", "logger",
    "current_layer_stack", "layer_scope", "LayerStackError",
    "StepBreakdown",
]
