"""The rows the held experts got over the rows of the dispatch buffers
the expert loop took, over the window: the held rows are the program's
own counter ``moe_rows_mean`` times the experts held; the buffers' rows
follow from its ``moe_turns`` (each a step's mean over the expert
layers, the window's end less its start) and the sizes the program gives
its buffers at the cell's tokens (``parallel/moe.py``: the first buffer
``_chunk_rows``, each after it ``_spill_rows``). The first buffer holds
twice what a uniform router sends, so about 0.5 is usual. It is the
share of the buffers that holds an expert's row, what the row kernel
moves and XLA's scatter-add pays for whole; it reads the same on either
path. A program without those counters reads nothing."""


def read(ctx):
    win = ctx["window"]
    a, b = win.at_open or {}, win.at_close or {}
    steps = b.get("steps", 0) - a.get("steps", 0)
    if "moe_rows_mean" not in b or "moe_turns" not in b or steps <= 0:
        return None
    turns = (b["moe_turns"] - a.get("moe_turns", 0.0)) / steps
    if turns <= 0:
        return None
    from paddle_tpu.parallel import moe
    m, mix = ctx["cfg"]["model"]["args"], ctx["mix"]
    experts = m.get("n_routed_experts") or m["num_experts"]
    held = m.get("experts_held") or experts
    tokens = int(mix["batch"]) // ctx["chips"] * int(mix["seq_len"])
    first = moe._chunk_rows(tokens, m["num_experts_per_tok"], experts, held)
    rows = (b["moe_rows_mean"] - a.get("moe_rows_mean", 0.0)) / steps
    return rows * held / (first + (turns - 1) * moe._spill_rows(first))
