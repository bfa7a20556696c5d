"""Operations and bytes the LSTM classifier's algorithm needs, from its
shapes alone. A backward pass costs two products for every forward one,
so forward + backward is three times the forward; recomputed work would
not count. All per sample (one sequence of ``seq_len`` tokens)."""

from __future__ import annotations


def _macs_per_token(m: dict) -> dict:
    e, h, layers = m["embed_dim"], m["hidden"], m["num_layers"]
    proj = sum((e if i == 0 else h) * 4 * h for i in range(layers))
    recurrent = layers * h * 4 * h
    return {"proj": proj, "recurrent": recurrent}


def step_flops_per_sample(cfg: dict, mix: dict) -> float:
    """Forward + backward FLOPs of one training sample."""
    m = cfg["model"]["args"]
    macs = _macs_per_token(m)
    per_token = macs["proj"] + macs["recurrent"]
    head = m["hidden"] * m["classes"]
    return 3 * 2 * (per_token * int(mix["seq_len"]) + head)


def param_count(cfg: dict) -> int:
    m = cfg["model"]["args"]
    e, h, layers = m["embed_dim"], m["hidden"], m["num_layers"]
    n = m["vocab_size"] * e + h * m["classes"] + m["classes"]
    for i in range(layers):
        n += (e if i == 0 else h) * 4 * h + 4 * h + h * 4 * h + 7 * h
    return n


def lstm_seq(cfg: dict, mix: dict, batch: int, itemsize: int = 4) -> dict:
    """The recurrences alone (every ``lstm<i>`` layer, forward and
    backward) for one step of ``batch`` rows on one device: FLOPs, and
    the bytes with the recurrent weight counted once a pass. Forward
    reads the projected input [T,B,4H] and the weight and writes the
    output [T,B,H]; backward reads the output's gradient, the saved
    gates, outputs and cell states and the weight, and writes the
    input's gradient and the weight's."""
    m = cfg["model"]["args"]
    h, layers, t = m["hidden"], m["num_layers"], int(mix["seq_len"])
    flops = 3 * 2 * layers * h * 4 * h * t * batch
    row = t * batch * h * itemsize           # one [T,B,H] array
    weight = h * 4 * h * itemsize
    forward = 4 * row + weight + row
    backward = row + 4 * row + 2 * row + weight + 4 * row + weight
    return {"flops": float(flops),
            "bytes": float(layers * (forward + backward))}
