"""Operations and bytes the algorithm needs, computed from shapes alone.

These are the numerators of the utilization and roofline metrics. They count
the work the mathematics requires, not what an implementation happens to do,
so every implementation of a layer is read against the same count.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def matmul_params(arch: dict) -> int:
    """Weights that enter a matmul in a forward pass of a decoder-only model.

    Attention projections, MLP matrices and the output head; the head is
    counted once, whether tied or not. An untied input-embedding table is a
    gather, not a matmul, and is left out. Biases and norm scales are left
    out (they are not matmul operands).
    """
    D, L, V = arch["d_model"], arch["n_layers"], arch["vocab"]
    H, KV, F = arch["n_heads"], arch["n_kv_heads"], arch["d_ff"]
    hd = arch.get("head_dim") or D // H
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    mlp = (3 if arch["mlp"] == "swiglu" else 2) * D * F
    return L * (attn + mlp) + D * V


def train_flops_per_token(arch: dict, seq: int) -> float:
    """Model FLOPs of one training token: 6 N_matmul + 12 L H hd S.

    PaLM (arXiv:2204.02311, App. B): forward and backward of every matmul
    weight, plus the attention score and value products at sequence length
    ``seq``. Recomputation (rematerialization) is not counted.
    """
    D, L, H = arch["d_model"], arch["n_layers"], arch["n_heads"]
    hd = arch.get("head_dim") or D // H
    return 6.0 * matmul_params(arch) + 12.0 * L * H * hd * seq


def reduce_min_bytes(
    tensors: Iterable[Tuple[int, int, int, int]], min_size: int
) -> int:
    """HBM bytes the ScaleCom reduce needs in one step, at least.

    ``tensors`` holds (elements, workers, gradient itemsize, residue
    itemsize) per tensor. Every tensor the reduce compresses (``elements >=
    min_size``) must have each worker's gradient and residue read and its
    residue written, and the reduced gradient written once; smaller tensors
    are averaged densely and are left out. Selection is a few compares per
    element, so this byte count, not an operation count, bounds the time.
    """
    total = 0
    for n, workers, g_bytes, r_bytes in tensors:
        if n >= min_size:
            total += n * (workers * (g_bytes + 2 * r_bytes) + g_bytes)
    return total
