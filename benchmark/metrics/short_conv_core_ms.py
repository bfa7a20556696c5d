"""Milliseconds a step of device time under ``sconv_core`` inside every
``*_sconv`` layer, forward, recomputed and backward: the two gates and
the depthwise causal convolution between the layer's two products,
where the compiler has not folded them into a product's fusion. Beside
its least time (``[B | C | X]`` read and the result written, about a
millisecond a layer at 8,192 tokens) it says what the core costs."""

from benchmark.metrics import scope_ms

SCOPE = r"jvp\(\w+_sconv\).*\bsconv_core\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
