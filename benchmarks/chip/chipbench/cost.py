"""Operations and bytes computed from shapes, and the table of peaks.

Model FLOPs count what the eps-net forward needs for one request row at its
true length: the projections, bidirectional attention over the row's valid
keys, the gated MLP, the time-conditioning MLP and the eps head. The LM
head is not part of the forward that the solve runs (the decode is counted
apart). Padding rows, spare tiles and bucket tails are never counted.
"""
from __future__ import annotations

import json
import pathlib
import re

_PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def layer_flops(m: dict, length: int) -> float:
    """FLOPs of one transformer layer of the dense family for one row of
    ``length`` positions (multiply-add = 2)."""
    d, hd = m["d_model"], m["head_dim"]
    q_dim, kv_dim = m["n_heads"] * hd, m["n_kv_heads"] * hd
    proj = 2 * length * d * (2 * q_dim + 2 * kv_dim)      # q, o and k, v
    window = m.get("sliding_window") or 0
    keys = min(length, window) if window else length
    attn = 2 * 2 * length * keys * q_dim                   # QK^T and PV
    mlp = 2 * length * d * m["d_ff"] * (3 if m.get("glu", True) else 2)
    return float(proj + attn + mlp)


def row_forward_flops(m: dict, length: int) -> float:
    """FLOPs of one eps-net forward of one row at its true ``length``."""
    d = m["d_model"]
    time_mlp = 2 * m["time_emb_dim"] * d + 2 * d * d
    eps_head = 2 * length * d * d
    return m["n_layers"] * layer_flops(m, length) + time_mlp + eps_head


def param_count(m: dict) -> int:
    """Parameters of the dense family as the program lays them out
    (untied LM head, time MLP and eps head of the diffusion objective)."""
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    q_dim, kv_dim = m["n_heads"] * hd, m["n_kv_heads"] * hd
    layer = d * (2 * q_dim + 2 * kv_dim) + d * f * (3 if m.get("glu", True)
                                                    else 2) + 2 * d
    te = m["time_emb_dim"]
    head = m["vocab_size"] * d * (1 if m.get("tie_embeddings") else 2)
    return (m["n_layers"] * layer + head + te * d + d + d * d + d + d * d
            + d)


# A custom call's operands as XLA prints them, e.g. ``f32[8,256,3840]{2,1,0}``
# or, placed in the core's own memory (VMEM) by XLA ahead of the call,
# ``f32[2,1,5]{2,1,0:T(1,128)S(1)}``
_SHAPE = re.compile(r"\b(bf16|f32|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]"
                    r"(\{[^}]*\})?")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}


def hbm_shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every ``dtype[dims]`` in an HLO instruction's text, in order, but
    those placed in memory space 1 (``S(1)``, the core's VMEM)."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims, layout in _SHAPE.findall(text)
            if "S(1)" not in layout]


def nbytes(dtype: str, dims: tuple[int, ...]) -> int:
    n = _BYTES[dtype]
    for x in dims:
        n *= x
    return n


def fused_ab_bytes(hlo_text: str) -> int | None:
    """HBM bytes one ``fused_ab_step`` call has to move, from the shapes of
    its Mosaic custom call (``%k = OUT custom-call(OPERANDS), ...``): the
    result (the new iterate and, with an error pair, the error partials)
    written once, and every operand (the per-row scalars, the iterate, the
    r-deep eps history, the noise when there is one) read once, except
    those XLA has already placed in VMEM (their HBM read is an async copy
    of its own, outside the call's time). None when the text holds no
    shape."""
    shapes = hbm_shapes(hlo_text.split(", custom_call_target")[0])
    if not shapes:
        return None
    return sum(nbytes(dt, dims) for dt, dims in shapes)
