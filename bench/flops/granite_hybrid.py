"""Operations and bytes of a granite-4.0-h training step, from its shapes.

Model FLOPs count what the forward and backward passes require: 6 per
matmul parameter per token (each Mamba layer's in- and out-projections,
each attention layer's Q, K, V and O, every layer's SwiGLU MLP and the tied
head; the embedding gather, the short conv and the norms not), causal
attention at 3x its forward over the S(S+1)/2 query-key pairs of each
attention layer, and the chunked SSD scan of each Mamba layer at 3x its
forward (``ssd_fwd_flops``). Operations recomputed under remat do not count.
"""
from __future__ import annotations

import re

from flops import dense

UNIT = "tokens"


class Ops:
    """A pattern over the trace's op texts that also keeps the name (the
    text before `` = ``) of every op it matched, for a reader to count."""

    def __init__(self, pattern: str):
        self.pattern, self.names = re.compile(pattern), set()

    def search(self, text: str):
        m = self.pattern.search(text)
        if m:
            self.names.add(text.partition(" = ")[0])
        return m


KERNELS = {
    # the Pallas flash kernels of kernels/flash_attention.py, by the name
    # each op of this step takes from its kernel (flash_fwd, flash_bwd_dkv,
    # flash_bwd_dq): a run of one attention layer is no loop, so its calls
    # stand in the step's own computation under their own names
    "flash": re.compile(r'^%flash_(fwd|bwd_dkv|bwd_dq)[.\d]* = .*custom_call_target="tpu_custom_call"'),
    # the SSD scan: XLA's loops over chunks, the only loops of the step that
    # carry a rank-4 float32 array (the (B, H, P, N) state, forward, or its
    # cotangent, backward) anywhere in their tuple type, which ends at
    # ") while("; and any op named for it, such as a later kernel
    "ssd": Ops(r"^%ssd|^%while[.\d]* = \([^\n]*?\bf32\[\d+,\d+,\d+,\d+\][^\n]*?\) while\("),
}


def items_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


def counts(cfg: dict) -> dict:
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return {k: kinds.count(k) for k in ("mamba", "attention")}


def mamba_runs(cfg: dict) -> int:
    """Runs of consecutive Mamba layers: the program scans each as one loop."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return sum(k == "mamba" and (i == 0 or kinds[i - 1] != k) for i, k in enumerate(kinds))


def check_ssd_loops(cfg: dict, names) -> None:
    """Raise unless the loops among the matched op ``names`` are three for
    each run of Mamba layers (the SSD's chunk loop forward, recomputed under
    remat, and backward, in the run's layer loop): a loop the pattern
    missed would read as a faster scan. None at all is a kernel's step."""
    loops = sorted(n for n in names if n.startswith("%while"))
    want = 3 * mamba_runs(cfg)
    if loops and len(loops) != want:
        raise RuntimeError(f"KERNELS['ssd'] matched {len(loops)} loops {loops}, not the "
                           f"{want} of {mamba_runs(cfg)} runs of Mamba layers")


def _ssm(cfg):
    d = cfg["hidden_size"]
    return (d, cfg["mamba_expand"] * d, cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_chunk_size"])


def matmul_params(cfg: dict) -> int:
    d, di, H, _, N, _ = _ssm(cfg)
    n = counts(cfg)
    mlp = 3 * d * cfg["intermediate_size"]
    attn = dense.matmul_params(dict(cfg, num_hidden_layers=1, vocab_size=0))  # with its MLP
    mamba = d * (2 * di + 2 * N + H) + di * d + mlp
    return n["mamba"] * mamba + n["attention"] * attn + d * cfg["vocab_size"]


def ssd_fwd_flops(cfg: dict, traffic: dict) -> float:
    """One Mamba layer's chunked SSD forward, per chunk of C tokens: C B^T
    and the decay-weighted products with x over the C(C+1)/2 causal pairs,
    the state read into y (C_t . S) and the state's update."""
    _, _, H, P, N, C = _ssm(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    pairs = C * (C + 1) // 2
    per_chunk = 2 * N * pairs + 2 * H * P * pairs + 4 * H * P * N * C
    return float(b * (s // C) * per_chunk)


def step_flops(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one training step of one job."""
    n = counts(cfg)
    return (6.0 * matmul_params(cfg) * items_per_step(cfg, traffic)
            + 3.0 * dense.attention_fwd_flops(dict(cfg, num_hidden_layers=n["attention"]), traffic)
            + 3.0 * n["mamba"] * ssd_fwd_flops(cfg, traffic))


def kernel_calls(cfg: dict, traffic: dict, kernel: str) -> list:
    """(FLOPs, HBM bytes) of each call a step makes to ``kernel``.

    flash: ``flops/dense.py``'s forward and backward calls, for each
    attention layer; no recomputed forward: the layer is no loop, and XLA
    takes the checkpointed forward's result from the step's own (the
    checkpoint leaves it free to). ssd: per
    Mamba layer the scan forward twice (the step's and its recomputation
    under remat) and its backward once; forward operations as
    ``ssd_fwd_flops``, backward twice them; bytes one read of each input
    (x, B, C in bfloat16, dt in float32) and one write of each output (y
    and the final state in float32; backward: dy in, dx, ddt, dB, dC out).
    """
    n = counts(cfg)
    if kernel == "flash":
        fwd, _, bwd = dense.kernel_calls(dict(cfg, num_hidden_layers=1), traffic, kernel)
        return [fwd, bwd] * n["attention"]
    assert kernel == "ssd", kernel
    _, di, H, P, N, _ = _ssm(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    x, dt, bc, y = b * s * di * 2, b * s * H * 4, 2 * b * s * N * 2, b * s * di * 4
    state = b * H * P * N * 4
    f = ssd_fwd_flops(cfg, traffic)
    fwd = (f, x + dt + bc + y + state)
    bwd = (2 * f, (x + dt + bc + y) + (x + dt + bc))
    return [fwd, fwd, bwd] * n["mamba"]
