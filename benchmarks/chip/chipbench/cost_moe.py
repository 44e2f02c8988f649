"""Operations and bytes of the sparse-expert (MoE) configurations.

Model FLOPs of one eps-net forward of one request row at its true length:
per layer the projections (q, k, v, o), bidirectional attention over the
row's valid keys, the router over all ``num_experts``, and the held
experts' SiLU-gated MLPs at the assignments they computed; then the time
MLP and the eps head, as :mod:`cost` counts them for the dense family. The
held experts' share is counted at ``assignments`` held-expert assignments
per position and layer, an average the program reports
(``Result.moe_assignments``); a position routed to none of them adds no
expert work. Padding rows, spare tiles and bucket tails are never counted.
"""
from __future__ import annotations

from . import cost


def expert_width(m: dict) -> int:
    return m["moe"]["expert_d_ff"] or m["d_ff"]


def layer_flops(m: dict, length: int, assignments: float) -> float:
    """FLOPs of one layer of a dropless MoE for one row of ``length``
    positions, at ``assignments`` held-expert assignments per position."""
    d = m["d_model"]
    dense_mlp = 2 * length * d * m["d_ff"] * 3
    attn = cost.layer_flops(m, length) - dense_mlp
    router = 2 * length * d * m["moe"]["num_experts"]
    experts = assignments * length * 2 * d * expert_width(m) * 3
    return float(attn + router + experts)


def row_forward_flops(m: dict, length: int, assignments: float) -> float:
    """FLOPs of one eps-net forward of one row at its true ``length``."""
    d = m["d_model"]
    time_mlp = 2 * m["time_emb_dim"] * d + 2 * d * d
    eps_head = 2 * length * d * d
    return (m["n_layers"] * layer_flops(m, length, assignments) + time_mlp
            + eps_head)


def param_count(m: dict) -> int:
    """Parameters as the program lays them out for one chip's share: the
    attention (with its q/k norm gains), the router over all experts and
    the held experts' stacks, per layer; the untied embedding and LM head,
    the time MLP and the eps head."""
    d, hd, f = m["d_model"], m["head_dim"], expert_width(m)
    moe = m["moe"]
    q_dim, kv_dim = m["n_heads"] * hd, m["n_kv_heads"] * hd
    held = moe["experts_held"] or moe["num_experts"]
    layer = (d * (2 * q_dim + 2 * kv_dim) + (2 * hd if m.get("qk_norm")
                                             else 0)
             + d * moe["num_experts"] + held * 3 * d * f + 2 * d)
    te = m["time_emb_dim"]
    head = m["vocab_size"] * d * (1 if m.get("tie_embeddings") else 2)
    return (m["n_layers"] * layer + head + te * d + d + d * d + d + d * d
            + d)


def expert_call_bytes(hlo_text: str) -> int | None:
    """HBM bytes one ``_moe_experts`` call moves, from the shapes of its
    Mosaic custom call: the result written once and every operand read
    once, but those XLA has placed in VMEM (``S(1)``). The weight operands
    are every layer's ``(layers, held, ...)`` stack, of which the call
    reads one layer's held experts: one layer is counted. None when the
    text holds no shape."""
    shapes = cost.hbm_shapes(hlo_text.split(", custom_call_target")[0])
    if not shapes:
        return None
    return sum(cost.nbytes(dt, dims[1:] if len(dims) == 4 else dims)
               for dt, dims in shapes)
