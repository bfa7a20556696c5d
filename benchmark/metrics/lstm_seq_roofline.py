"""The LSTM recurrences' share of their roofline: the least time the
chip could take for every ``lstm<i>`` layer's forward and backward of
one step (``counts.lstm_seq``: max of FLOPs over the bf16 peak and bytes
over the HBM peak, the recurrent weight counted once a pass) over the
device time of every operation under those layers' scopes, whatever
implements them (Pallas kernel or ``lax.scan``). The bound that sets the
least time is printed on standard error."""

import sys

from benchmark import peaks

SCOPE = r"jvp\(lstm\d+\)"


def read(ctx):
    trace, counts = ctx["trace"], ctx["counts"]
    if trace is None or ctx["peak"] is None or \
            not hasattr(counts, "lstm_seq"):
        return None
    steps = ctx["window"].steps
    seconds = trace.scope_seconds(SCOPE)
    if steps <= 0 or seconds <= 0:
        return None
    need = counts.lstm_seq(ctx["cfg"], ctx["mix"],
                           int(ctx["mix"]["batch"]) // ctx["chips"])
    least, bound = peaks.least_seconds(need["flops"], need["bytes"],
                                       ctx["peak"])
    print(f"[bench] lstm_seq_roofline: {1e3 * seconds / steps:.3f} ms a "
          f"step under the lstm scopes, least {1e3 * least:.3f} ms, bound "
          f"by {bound}", file=sys.stderr)
    return 100.0 * least * steps / seconds
