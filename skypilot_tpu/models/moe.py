"""Mixture-of-Experts MLP with expert parallelism over the ``expert`` axis.

The reference delegates MoE (like every parallelism strategy) to launched
workloads (SURVEY.md §2.11); here it is a first-class layer.  The design is
the GShard/Switch einsum formulation, which is the TPU-idiomatic one:

* routing, dispatch, and combine are dense one-hot einsums — MXU work with
  static shapes, no gather/scatter, no dynamic shapes that would defeat XLA;
* the dispatched activations ``[experts, capacity, d_model]`` carry an
  ``expert`` logical axis; with the expert dim sharded over the ``expert``
  mesh axis, XLA SPMD inserts the all-to-all between the token-sharded and
  expert-sharded layouts automatically (sharding-annotation recipe — we
  never hand-write the collective);
* per-expert FFNs run as one batched einsum over the expert dim (vmap-free,
  one big MXU contraction).

Capacity-based token dropping (``capacity_factor``) keeps shapes static;
the Switch-style load-balancing aux loss pushes the router toward uniform
expert utilization so drops stay rare.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import decode_attention, grouped_matmul

Params = Dict[str, Any]


def init_moe_params(key: jax.Array, d_model: int, d_ff: int,
                    num_experts: int, dtype: Any) -> Params:
    ks = jax.random.split(key, 4)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) *
                (fan_in ** -0.5)).astype(dtype)

    return {
        # Router stays fp32: tiny, and routing decisions are precision-
        # sensitive.
        'router': jax.random.normal(ks[0], (d_model, num_experts),
                                    jnp.float32) * (d_model ** -0.5),
        'we_gate': dense(ks[1], (num_experts, d_model, d_ff), d_model),
        'we_up': dense(ks[2], (num_experts, d_model, d_ff), d_model),
        'we_down': dense(ks[3], (num_experts, d_ff, d_model), d_ff),
    }


def moe_logical_axes() -> Params:
    return {
        'router': ('embed', None),
        'we_gate': ('expert', 'embed', 'mlp'),
        'we_up': ('expert', 'embed', 'mlp'),
        'we_down': ('expert', 'mlp', 'embed'),
    }


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert slot count, rounded up to a multiple of 8 so the
    capacity dim tiles cleanly on the MXU/VPU."""
    cap = math.ceil(top_k * num_tokens / num_experts * capacity_factor)
    return max(8, -(-cap // 8) * 8)


def moe_mlp(x: jax.Array, params: Params, num_experts: int, top_k: int,
            capacity_factor: float,
            constrain=None,
            token_mask=None) -> Tuple[jax.Array, jax.Array]:
    """``x: [B, S, D] -> ([B, S, D], aux_loss)``.

    Dispatch priority is choice-major (all first choices across tokens beat
    any second choice), matching GShard's overflow semantics.

    ``token_mask`` ([B, S], 1 = real token) excludes positions from routing
    entirely: masked tokens consume NO expert capacity (they are dropped
    before the capacity cumsum) and produce zero output. Serving batches
    with right-padded rows must pass it, or junk padded positions compete
    for capacity slots and can displace other rows' real tokens.
    """
    b, s, d = x.shape
    n = b * s
    e, k = num_experts, top_k
    cap = expert_capacity(n, e, k, capacity_factor)
    xf = x.reshape(n, d)

    logits = xf.astype(jnp.float32) @ params['router']        # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)             # [N, K]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    choice_hot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # [N, K, E]
    if token_mask is not None:
        m = token_mask.reshape(n).astype(jnp.float32)
        gate_vals = gate_vals * m[:, None]
        choice_hot = choice_hot * m[:, None, None]

    # Position of each (token, choice) in its expert's buffer: cumulative
    # count in choice-major order.
    flat = choice_hot.transpose(1, 0, 2).reshape(k * n, e)
    pos = jnp.cumsum(flat, axis=0) - 1.0
    keep = flat * (pos < cap)
    pos = pos.reshape(k, n, e).transpose(1, 0, 2)             # [N, K, E]
    keep = keep.reshape(k, n, e).transpose(1, 0, 2)

    slot_hot = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                              dtype=jnp.float32) * keep[..., None]
    dispatch = slot_hot.sum(axis=1)                           # [N, E, C]
    combine = jnp.einsum('nk,nkec->nec', gate_vals, slot_hot)  # [N, E, C]

    # Token-sharded -> expert-sharded: XLA inserts the all-to-all here once
    # expert_in's expert dim is pinned to the `expert` mesh axis by the
    # caller-provided constraint (falling back to propagation from the
    # we_* param shardings when no mesh is in scope).
    expert_in = jnp.einsum('nec,nd->ecd', dispatch,
                           xf.astype(jnp.float32)).astype(x.dtype)
    if constrain is not None:
        expert_in = constrain(expert_in)
    gate = jnp.einsum('ecd,edf->ecf', expert_in, params['we_gate'])
    up = jnp.einsum('ecd,edf->ecf', expert_in, params['we_up'])
    expert_out = jnp.einsum('ecf,efd->ecd', jax.nn.silu(gate) * up,
                            params['we_down'])
    out = jnp.einsum('nec,ecd->nd', combine,
                     expert_out.astype(jnp.float32))

    # Switch aux loss: E * sum_e f_e * P_e — minimized at uniform routing.
    frac_dispatched = choice_hot[:, 0, :].mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(frac_dispatched * mean_prob)
    return out.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Drop-free routed experts (the serving path of sigmoid-routed models,
# models/mla_moe.py). Beside the capacity path above, which training's
# ``moe_mlp`` keeps: here no token is ever dropped, so co-batched rows do
# not couple and every engine feature that needs row independence
# composes (``model_ops.ModelOps.rows_couple`` is False).


def route_sigmoid(x: jax.Array, router: jax.Array, bias: jax.Array,
                  top_k: int, scale: float, norm: bool = True
                  ) -> Tuple[jax.Array, jax.Array]:
    """``x [N, d]`` -> (expert ids [N, K] int32, weights [N, K] float32).
    Scores ``s = sigmoid(x W_g)`` in float32 (bfloat16 operands multiply
    exactly into the float32 accumulator); the K experts with the largest
    ``s + bias`` are taken (the bias selects, it does not weigh); weights
    ``scale * s_sel / (sum s_sel + 1e-20)``."""
    with jax.named_scope('moe.route'):
        logits = jnp.einsum('nd,de->ne', x, router.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scale


# Rows a held expert gets in a call, on average (M over the held groups),
# from which the experts' grouped matmuls take ``grouped_matmul.moe_gmm``.
# Alone on a v5e (PERF.md §6, PR 39) XLA's ``ragged_dot`` reads 423 GB/s
# of weights at 3 rows an expert and 259 from 8 on; the kernel 665-694
# at either. Below 8 are the decode steps, whose calls are mostly junk
# rows of empty slots: not timed, so left to ``ragged_dot``.
GROUPED_KERNEL_ROWS = 8
# The kernel's row tile: the rows an expert gets, in whole bf16 sublane
# tiles, and no more than this.
GROUPED_MAX_TILE = 128


def row_tile(m: int, groups: int) -> int:
    """``moe_gmm``'s row tile for ``m`` sorted rows over ``groups``
    experts: the mean rows an expert gets, rounded down to a power of
    two, between bf16's 16-row sublane tile and ``GROUPED_MAX_TILE``."""
    mean = max(m // max(groups, 1), 1)
    return min(GROUPED_MAX_TILE, max(16, 1 << (mean.bit_length() - 1)))


def grouped_path(m: int, groups: int, k: int, n: int, dtype) -> str:
    """How a drop-free expert layer's grouped matmuls run over ``m``
    sorted rows, ``groups`` held experts, [m, k] x [k, n] in ``dtype``:
    ``'kernel'`` (``grouped_matmul.moe_gmm``: each expert with rows read
    once, at the HBM rate whatever its rows) or ``'ragged_dot'``
    (XLA's). ``paged.decode_path``'s rule: a TPU, or the interpreter
    where a test asks for it by name, and shapes the kernel takes; and
    ``GROUPED_KERNEL_ROWS`` rows an expert or more, where XLA's falls
    behind. The ONE definition: ``dropfree_mlp`` branches on it and the
    engine reports it (``stats()['moe_grouped']``)."""
    if not (attention_ops._use_pallas()
            or decode_attention.PAGED_INTERPRET):
        return 'ragged_dot'
    if m < GROUPED_KERNEL_ROWS * groups:
        return 'ragged_dot'
    if grouped_matmul.gmm_fits(row_tile(m, groups), k, n, dtype):
        return 'kernel'
    attention_ops.log_fallback_once(
        'moe_gmm', (m, groups, k, n),
        f'{jnp.dtype(dtype).name} [{k}, {n}] outside gmm_fits()')
    return 'ragged_dot'


def dropfree_mlp(x: jax.Array, params: Params, top_k: int, scale: float,
                 norm: bool = True, held: Optional[Tuple[int, int]] = None,
                 token_mask: Optional[jax.Array] = None,
                 stack_layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """``x [N, d]`` -> (``[N, d]``, per-expert token counts [E] int32).

    Route over ALL ``E`` experts the router has, sort the ``N * K``
    (token, choice) pairs by expert, run the experts HELD here
    (``held = (lo, hi)``, a contiguous range; None = all; ``we_*`` hold
    exactly those, ``[hi - lo, ...]``) as grouped matmuls over the
    sorted rows (``grouped_path`` says how: ``grouped_matmul.moe_gmm``
    where experts get many rows, else ``jax.lax.ragged_dot``; either
    reads an expert's weights only if a row is routed to it), and
    un-sort with the routing weights. Pairs routed to experts
    held elsewhere sort last, belong to no group and add nothing: their
    part is another chip's. A shared expert (``ws_*``, if present) takes
    every token. ``token_mask [N]`` keeps junk rows (a freed slot still
    decodes) out of every group, so they read no expert's weights, and
    out of the counts.

    ``stack_layer`` (an int32 scalar, traced): ``we_*`` are the WHOLE
    stack of a scanned run of layers, ``[L, hi - lo, ...]``, and this is
    layer ``stack_layer`` of it. The grouped matmuls then take the stack
    as ``L * (hi - lo)`` groups of which only this layer's are not
    empty. Slicing the layer out instead puts a copy of its experts in
    front of each Mosaic call: on a v5e that was 64% of a decode step
    (PERF.md, PR 28)."""
    n, d = x.shape
    e = params['router'].shape[-1]
    lo, hi = held or (0, e)
    idx, w = route_sigmoid(x, params['router'], params['router_bias'],
                           top_k, scale, norm)
    with jax.named_scope('moe.dispatch'):
        local = idx - lo
        here = (local >= 0) & (local < hi - lo)
        if token_mask is not None:
            here = here & token_mask[:, None].astype(bool)
        # elsewhere/junk -> one past the last held expert: sorts last
        flat = jnp.where(here, local, hi - lo).reshape(-1)
        order = jnp.argsort(flat)                    # stable
        sizes = jnp.bincount(flat, length=hi - lo + 1)[:-1].astype(jnp.int32)
        xs = x[order // top_k]
        counted = (idx if token_mask is None else
                   jnp.where(token_mask[:, None].astype(bool), idx, e))
        load = jnp.bincount(counted.reshape(-1),
                            length=e + 1)[:-1].astype(jnp.int32)
    with jax.named_scope('moe.experts'):
        we = [params[k] for k in ('we_gate', 'we_up', 'we_down')]
        if stack_layer is not None:
            n_l = we[0].shape[0]
            we = [w.reshape((n_l * (hi - lo),) + w.shape[2:]) for w in we]
        m = n * top_k
        if grouped_path(m, hi - lo, d, we[0].shape[-1], x.dtype) == 'kernel':
            tm = row_tile(m, hi - lo)
            xs = jnp.pad(xs, ((0, -m % tm), (0, 0)))

            def mm(a, w):
                return grouped_matmul.moe_gmm(
                    a, w, sizes, stack_layer, tm=tm,
                    interpret=not attention_ops._use_pallas())
        else:
            groups = sizes
            if stack_layer is not None:
                groups = jax.lax.dynamic_update_slice(
                    jnp.zeros((we[0].shape[0],), jnp.int32), sizes,
                    (stack_layer * (hi - lo),))

            def mm(a, w):
                return jax.lax.ragged_dot(a, w, groups)
        gate = mm(xs, we[0])
        up = mm(xs, we[1])
        out = mm(jax.nn.silu(gate) * up, we[2])[:m]
    with jax.named_scope('moe.dispatch'):
        # rows past the last group are not written by ragged_dot (the
        # kernel writes zeros): take them as zero, whatever it holds
        in_group = jnp.arange(n * top_k) < jnp.sum(sizes)
        out = jnp.where(in_group[:, None], out, 0)
        back = out[jnp.argsort(order)].reshape(n, top_k, d)
        y = jnp.einsum('nk,nkd->nd', jnp.where(here, w, 0.0),
                       back.astype(jnp.float32))
    if 'ws_gate' in params:
        with jax.named_scope('moe.shared'):
            g = jnp.einsum('nd,df->nf', x, params['ws_gate'])
            u = jnp.einsum('nd,df->nf', x, params['ws_up'])
            y = y + jnp.einsum('nf,fd->nd', jax.nn.silu(g) * u,
                               params['ws_down'],
                               preferred_element_type=jnp.float32)
    return y.astype(x.dtype), load
