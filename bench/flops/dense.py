"""Operations and bytes of a dense GQA decoder training step, from its shapes.

Model FLOPs count what the forward and backward passes require: 6 per
matmul parameter per token (the tied head included, the embedding gather
not), and causal attention at 3x its forward (2 matmuls forward, 4
backward) over the S(S+1)/2 query-key pairs. Operations recomputed under
remat do not count.
"""
from __future__ import annotations

import re

UNIT = "tokens"
# the Pallas flash-attention kernels of kernels/flash_attention.py. A device
# op's name in the trace is its HLO text; the Pallas calls are the custom
# calls with target tpu_custom_call, and in this family's step they are the
# flash kernels alone (a v5e trace shows four per layer: the forward, its
# recomputation under remat, and the two backward kernels). Other custom
# calls (AllocateBuffer, ConcatBitcast) and ops that merely take a custom
# call's result as an operand do not match.
KERNELS = {"flash": re.compile(r'custom_call_target="tpu_custom_call"')}


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h


def items_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


def matmul_params(cfg: dict) -> int:
    d, h, kvh, hd = _dims(cfg)
    attn = d * h * hd * 2 + d * kvh * hd * 2
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def _pairs(s: int) -> int:
    return s * (s + 1) // 2


def attention_fwd_flops(cfg: dict, traffic: dict) -> float:
    """QK^T and PV over the causal pairs, every layer, every sequence."""
    _, h, _, hd = _dims(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    return 4.0 * b * h * hd * _pairs(s) * cfg["num_hidden_layers"]


def step_flops(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one training step of one job."""
    return (6.0 * matmul_params(cfg) * items_per_step(cfg, traffic)
            + 3.0 * attention_fwd_flops(cfg, traffic))


def kernel_calls(cfg: dict, traffic: dict, kernel: str) -> list:
    """(FLOPs, HBM bytes) of each call a step makes to ``kernel``.

    Per layer: the forward twice (the step's forward and its recomputation
    under remat) and the backward once. Operations are those the algorithm
    needs (2 matmuls forward; 5 backward: scores again, dP, dV, dK, dQ);
    bytes are one read of each input and one write of each output (bf16
    tensors, one float32 log-sum-exp per query row).
    """
    assert kernel == "flash", kernel
    _, h, kvh, hd = _dims(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    qo = b * s * h * hd * 2  # one (B, S, H, D) bf16 tensor
    kv = b * s * kvh * hd * 2
    lse = b * s * h * 4
    mm = 2.0 * b * h * hd * _pairs(s)  # one causal matmul
    fwd = (2 * mm, 2 * qo + 2 * kv + lse)
    bwd = (5 * mm, 4 * qo + 4 * kv + lse)  # q o do dq, k v dk dv, lse
    return [fwd, fwd, bwd] * cfg["num_hidden_layers"]
