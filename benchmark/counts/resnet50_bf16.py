"""Operations ResNet-50's algorithm needs, from the paper's shapes (He
et al., table 1: 224 -> 112 -> 56 -> 28 -> 14 -> 7). The program's pool
rounds 112/2 up to 57, so its four stages run on maps of 57, 29, 15 and
8 and multiply 3.6, 7.3, 14.8 and 30.6% more than is counted here,
13.6% more over the whole network (4.38 GMAC an image against 3.86):
``step_mfu_pct`` counts the paper's work, so the chip's arithmetic units
are that much busier than it says. That is the program's to win back,
not the count's to grant."""

from __future__ import annotations

STAGES = (3, 4, 6, 3)


def _convs(m: dict):
    """(filter, in, out, output size) of every convolution."""
    width, c, size = m["width"], m["channels"], m["image_size"]
    size = (size + 2 * 3 - 7) // 2 + 1          # stem 7x7/2
    out = [(7, c, width, size)]
    size = (size + 2 - 3) // 2 + 1              # 3x3/2 max-pool
    nf, cin = width, width
    for stage, n in enumerate(STAGES):
        for i in range(n):
            if stage > 0 and i == 0:
                size = (size - 1) // 2 + 1      # stride 2 on the first 1x1
            out += [(1, cin, nf, size), (3, nf, nf, size),
                    (1, nf, nf * 4, size)]
            if i == 0:
                out.append((1, cin, nf * 4, size))
            cin = nf * 4
        nf *= 2
    return out, cin


def forward_macs_per_sample(cfg: dict) -> float:
    m = cfg["model"]["args"]
    convs, cin = _convs(m)
    macs = sum(fs * fs * ci * co * size * size for fs, ci, co, size in convs)
    return float(macs + cin * m["classes"])


def step_flops_per_sample(cfg: dict, mix: dict) -> float:
    """Forward + backward FLOPs of one training image."""
    return 3 * 2 * forward_macs_per_sample(cfg)


def param_count(cfg: dict) -> int:
    m = cfg["model"]["args"]
    convs, cin = _convs(m)
    n = sum(fs * fs * ci * co + 2 * co for fs, ci, co, _ in convs)
    return n + cin * m["classes"] + m["classes"]
