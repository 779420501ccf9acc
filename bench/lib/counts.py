"""Operations and bytes the benchmark's computations need, from shapes.

A kernel's count is the work the computation needs, whatever implements
it: a later kernel that reads the table fewer times shows as a higher
roofline share, with no change here.
"""

from __future__ import annotations


def backbone_flops(m: dict, tokens: int) -> float:
    """FLOPs of one frame through the tapped transformer: 2 x matmul
    parameters x positions for the projections and the MLP, the causal
    attention score and value products (S(S+1)/2 pairs per head each),
    the tap projections and the class head."""
    d, h, hk = m["d_model"], m["num_heads"], m["kv_heads"]
    hd = m.get("head_dim") or d // h
    S = m["frontend_len"] + tokens
    ff = m["d_ff"]
    mlp_mats = 2 if m.get("act", "swiglu") == "gelu" else 3
    proj = 2 * S * d * hd * (h + 2 * hk) + 2 * S * h * hd * d
    attn = 2 * 2 * h * hd * S * (S + 1) / 2
    mlp = 2 * S * d * ff * mlp_mats
    k = m["tap_every"]
    n_taps = len(range(k - 1, m["num_layers"], k)) if k > 0 else 0
    heads = 2 * n_taps * d * m["sem_dim"] + 2 * d * m["num_classes"]
    return m["num_layers"] * (proj + attn + mlp) + heads


def lookup_work(K: int, B: int, L: int, I: int, d: int,
                entry_bytes: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of Eq.-1/2 lookups of K tables, B taps each: the
    table once, the taps once, scores and exits out."""
    flops = 2.0 * K * B * L * I * d + 4.0 * K * B * L * I
    bytes_ = (K * L * I * d * entry_bytes + K * B * L * d * 4
              + K * B * (2 * L + 1) * 4)
    return flops, float(bytes_)


def merge_work(K: int, L: int, I: int, d: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the Eq.-4/5 merge of K uploads: the table read
    and written once, each upload's U, touched cells and φ read once."""
    flops = K * L * I * (10.0 * d)
    bytes_ = (2 * L * I * d * 4 + 2 * I * 4
              + K * (L * I * d * 4 + L * I + I * 4))
    return flops, float(bytes_)


def round_flops(K: int, F: int, L: int, I: int, d: int) -> float:
    """FLOPs of one collaborative round: the lookups, the Eq.-3
    absorptions (normalise and add over L x d per frame) and the merge."""
    look, _ = lookup_work(K, F, L, I, d)
    absorb = K * F * L * 4.0 * d
    merge, _ = merge_work(K, L, I, d)
    return look + absorb + merge


def roofline_s(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bounds."""
    return max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])
