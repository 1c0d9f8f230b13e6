"""Operations and bytes of the Kimi-VL training step, from the configuration
file alone (``reference_vlm.sizes``); the program is never asked.

A multiply-add counts as two operations. Training counts each product three
times (forward, input gradient, weight gradient), but the patch embedding,
whose input is the frame, twice. Causal attention counts the query-key
pairs it needs, S (S + 1) / 2 of them; the output head the text positions
only. Routed experts count the work done on this chip: each token's
``num_experts_per_tok`` slots, of which ``n_routed_experts / router_width``
land on held experts when the router spreads them evenly. Recomputed
(rematerialised) work does not count.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import reference_vlm


def forward_macs(cfg: Dict, seq_len: int) -> Dict[str, float]:
    """Multiply-adds of one sequence's forward pass, by stage."""
    s = reference_vlm.sizes(cfg)
    D, H = s["D"], s["H"]
    N = s["g"] ** 2                                 # patches of the frame
    n = N // s["k"] ** 2                            # image tokens
    Dv, Fv, Dm = s["Dv"], s["Fv"], s["Dv"] * s["k"] ** 2
    S = seq_len
    vision = s["Lv"] * (N * (Dv * 3 * Dv + Dv * Dv + 2 * Dv * Fv) + 2 * N * N * Dv)
    mla = (s["Ld"] + s["Lm"]) * (
        S * (D * H * (s["dn"] + s["dr"]) + D * (s["R"] + s["dr"])
             + s["R"] * H * (s["dn"] + s["dv"]) + H * s["dv"] * D)
        + S * (S + 1) // 2 * H * (s["dn"] + s["dr"] + s["dv"]))
    return {
        "patch": N * s["P"] ** 2 * 3 * Dv,
        "vision": vision,
        "projector": n * (Dm * Dm + Dm * D),
        "mla": mla,
        "dense_mlp": s["Ld"] * S * 3 * D * s["F"],
        "moe.router": s["Lm"] * S * D * s["E"],
        "moe.shared": s["Lm"] * S * 3 * D * s["Fs"],
        "moe.routed": s["Lm"] * S * s["K"] * s["G"] / s["E"] * 3 * D * s["Fe"],
        "lm_head": (S - n) * D * s["V"],
    }


def train_flops(cfg: Dict, seq_len: int) -> float:
    """Operations of one sequence's (one frame's) training step."""
    macs = forward_macs(cfg, seq_len)
    return 2 * (3 * sum(macs.values()) - macs["patch"])


def routed_gmm_products(cfg: Dict, slots: int, layer_steps: int
                        ) -> List[Tuple[float, float]]:
    """``(operations, bytes)`` of each grouped matmul of the routed experts
    in training, over ``slots`` token-slots sent to held experts in
    ``layer_steps`` (MoE layer, step) pairs: for each of the gate, up and
    down products the forward, the input gradient and the weight gradient.
    Bytes count each operand once: the slots' rows in bfloat16 in, float32
    out; the held experts' weights (bfloat16 in, float32 as a gradient)
    once for each pair."""
    s = reference_vlm.sizes(cfg)
    D, F, G = s["D"], s["Fe"], s["G"]
    out = []
    for k, n in ((D, F), (D, F), (F, D)):
        ops = 2.0 * slots * k * n
        w = layer_steps * G * k * n
        out.append((ops, 2 * slots * k + 2 * w + 4 * slots * n))   # y = x w
        out.append((ops, 2 * slots * n + 2 * w + 4 * slots * k))   # dx
        out.append((ops, 2 * slots * k + 2 * slots * n + 4 * w))   # dw
    return out


def routed_gmm_least_seconds(cfg: Dict, slots: int, layer_steps: int,
                             peak: Dict) -> float:
    """The least time the chip could spend in those products: each one's
    larger of operations over peak FLOP/s and bytes over peak bytes/s."""
    return sum(max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
               for ops, nbytes in routed_gmm_products(cfg, slots, layer_steps))
