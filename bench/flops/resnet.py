"""Operations of a ResNet-V2 classifier training step, from its shapes.

Model FLOPs count each convolution and the head at 2 per multiply-add,
3x forward for forward and backward, except the stem, whose input needs
no gradient (2x). Normalisation and activations are not counted.
"""
from __future__ import annotations

UNIT = "images"
KERNELS = {}


def items_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"]


def _convs(cfg: dict):
    """(output side, kernel side, cin, cout) of every convolution after the stem."""
    s = cfg["image_size"]
    s = s if s <= 32 else -(-s // 4)  # 7x7/2 stem, then 3x3/2 pool
    w0, cin = cfg["base_width"], cfg["base_width"]
    for stage, n in enumerate(cfg["stages"]):
        width = w0 * 2**stage
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            out = -(-s // stride)
            yield s, 1, cin, width
            yield out, 3, width, width
            yield out, 1, width, width * 4
            if cin != width * 4:
                yield out, 1, cin, width * 4
            cin, s = width * 4, out


def stem_flops(cfg: dict) -> float:
    s = cfg["image_size"]
    k, out = (3, s) if s <= 32 else (7, -(-s // 2))
    return 2.0 * out * out * k * k * 3 * cfg["base_width"]


def forward_flops(cfg: dict) -> float:
    """Forward FLOPs of one image."""
    convs = sum(2.0 * o * o * k * k * ci * co for o, k, ci, co in _convs(cfg))
    c = cfg["base_width"] * 2 ** (len(cfg["stages"]) - 1) * 4
    return stem_flops(cfg) + convs + 2.0 * c * cfg["num_classes"]


def step_flops(cfg: dict, traffic: dict) -> float:
    return traffic["batch"] * (3.0 * forward_flops(cfg) - stem_flops(cfg))
