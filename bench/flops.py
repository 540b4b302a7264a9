"""Operations and bytes of the agent's device programs, from its shapes.

Counts the multiply-adds of every matrix product (2 operations each); the
element-wise work of norms, softmax and GELU is left out, so a share of a
peak computed from these counts errs low, never high. Bytes are the least
a call must move through HBM: the parameters it reads once, in the dtype
they are stored in (float32), and its inputs and outputs.
"""
from __future__ import annotations

from typing import Dict

STATE_DIM = 40
PARAM_BYTES = 4
IO_BYTES = 4


def trunk_pass_flops(a: Dict) -> float:
    """One encoder pass over one observation (k snapshots)."""
    k, d, f = a["history"], a["d_model"], a["d_ff"]
    embed = 2 * k * (STATE_DIM + 1) * d
    qkvo = 4 * 2 * k * d * d
    attn = 2 * 2 * k * k * d
    mlp = 2 * 2 * k * d * f
    return float(embed + a["n_layers"] * (qkvo + attn + mlp))


def passes(a: Dict, head: str) -> int:
    """Encoder passes per observation: the V-head runs once per action,
    the P-head once; a ``moe`` agent runs every expert."""
    per_expert = 2 if head == "q" else 1
    return per_expert * (a["n_experts"] if a["kind"] == "moe" else 1)


def forward_flops(a: Dict, head: str, batch: int) -> float:
    return batch * passes(a, head) * trunk_pass_flops(a)


def param_count(a: Dict) -> int:
    k, d, f, L = a["history"], a["d_model"], a["d_ff"], a["n_layers"]
    layer = 4 * d * d + 2 * d * f + 4 * d
    # embedding, positions, layers, final norm, V- and P-heads, and the
    # encoder's own unused two-way output head
    expert = (STATE_DIM + 1) * d + k * d + L * layer + 2 * d + d + 2 * d + 2 * d
    if a["kind"] == "moe":
        return a["n_experts"] * expert + (STATE_DIM + 1) * a["n_experts"]
    return expert


def forward_bytes(a: Dict, batch: int) -> float:
    return (param_count(a) * PARAM_BYTES
            + batch * a["history"] * STATE_DIM * IO_BYTES + batch * 2 * IO_BYTES)


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The roofline: the larger of operations over peak rate and bytes over
    peak bandwidth."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
