"""Model arithmetic shared by the rooflines and the MFU figures.

All counts are of what the ALGORITHM needs (2 FLOPs a multiply-add);
recomputation, padding and copies are the implementation's and count
for nothing.
"""
from __future__ import annotations

from typing import Any, Dict


def dims(cfg: Dict[str, Any]):
    d, L = cfg['hidden_size'], cfg['num_hidden_layers']
    hq, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    hd = cfg.get('head_dim') or d // hq
    return d, L, hq, hkv, hd, cfg['intermediate_size'], cfg['vocab_size']


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that a token is multiplied by: every projection and
    the output head. The embedding is a lookup and the norms are
    vectors: neither counts.
    N = L*(d*Hq*D*2 + d*Hkv*D*2 + 3*d*F) + d*V"""
    d, L, hq, hkv, hd, f, v = dims(cfg)
    return L * (2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f) + d * v


def weight_bytes(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """Bytes of every weight a decode step reads once: the matmul
    parameters plus the norms; of the embedding only one row per
    sequence, which is nothing beside the rest."""
    d, L, *_ = dims(cfg)
    return (matmul_params(cfg) + (2 * L + 1) * d) * bytes_per


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per: int = 2) -> int:
    """K and V of one position over all layers: 2*L*Hkv*D*bytes."""
    _, L, _, hkv, hd, _, _ = dims(cfg)
    return 2 * L * hkv * hd * bytes_per


def forward_flops(cfg: Dict[str, Any], new_tokens: float,
                  attended: float) -> float:
    """FLOPs of a forward pass over ``new_tokens`` positions:
    2*N per token, plus attention's 4*L*Hq*D per (query, key) pair —
    ``attended`` is the number of such pairs summed over the tokens
    (a token at position p attends p+1 keys)."""
    _, L, hq, _, hd, _, _ = dims(cfg)
    return 2.0 * matmul_params(cfg) * new_tokens + 4.0 * L * hq * hd * attended


def causal_pairs(start: int, n: int) -> float:
    """(query, key) pairs of ``n`` consecutive positions from
    ``start``: sum_{p=start}^{start+n-1} (p+1)."""
    return n * start + n * (n + 1) / 2.0


def train_flops_per_step(cfg: Dict[str, Any], batch: int, seq: int) -> float:
    """3 x the forward pass (forward, and two products per product in
    the backward): 6*N*tokens + 12*L*Hq*D*pairs. Rematerialization is
    not counted."""
    return 3.0 * batch * forward_flops(cfg, seq, causal_pairs(0, seq))
