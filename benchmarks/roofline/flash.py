"""The three flash-attention kernels of the train step, causal, one
call over [B, Hq, S, D] queries and [B, Hkv, S, D] keys and values.

Causal attention needs half the (query, key) pairs: S*(S+1)/2 ~ S^2/2.
A product over a pair is 2*D FLOPs.

fwd : QK^T and PV                      -> 2 products : 4*D*pairs
dq  : QK^T (recompute), dP=dO V^T, dQ=dS K -> 3 products : 6*D*pairs
dkv : QK^T (recompute), dP=dO V^T, dV=P^T dO, dK=dS^T Q -> 4 : 8*D*pairs
(pairs = B*Hq*S*(S+1)/2)

Bytes are the operands read once and the results written once, in
bf16 (the log-sum-exp and delta rows in float32 are 1/D of that and
left out):
fwd : q, k, v, o            dq : q, k, v, do, dq
dkv : q, k, v, do, dk, dv
"""
from __future__ import annotations

from typing import Tuple


def _sizes(b: int, hq: int, hkv: int, s: int, d: int, bytes_per: int = 2):
    q = b * hq * s * d * bytes_per
    kv = b * hkv * s * d * bytes_per
    pairs = b * hq * s * (s + 1) / 2.0
    return q, kv, pairs


def fwd(b, hq, hkv, s, d) -> Tuple[float, float]:
    q, kv, pairs = _sizes(b, hq, hkv, s, d)
    return 4.0 * d * pairs, 2 * q + 2 * kv


def dq(b, hq, hkv, s, d) -> Tuple[float, float]:
    q, kv, pairs = _sizes(b, hq, hkv, s, d)
    return 6.0 * d * pairs, 3 * q + 2 * kv


def dkv(b, hq, hkv, s, d) -> Tuple[float, float]:
    q, kv, pairs = _sizes(b, hq, hkv, s, d)
    return 8.0 * d * pairs, 2 * q + 4 * kv


KERNELS = {'flash_fwd': fwd, 'flash_dq': dq, 'flash_dkv': dkv}
