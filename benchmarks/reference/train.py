"""The plain reference of the train step: next-token loss, its
gradient, global-norm clipping and Adafactor, written out in
``jax.numpy`` float32. It imports nothing of the program (and not
optax either: the optimizer is part of what the program's step is
compared with).

What the configuration states and this follows:

* loss: mean cross-entropy of positions ``0..S-2`` predicting tokens
  ``1..S-1``, over all rows;
* parameters are STORED in bfloat16 (the configuration's dtype): the
  update is computed in float32 from the float32 value of the stored
  parameter and the result is rounded to bfloat16, round to nearest
  even. A bfloat16 parameter moves only where the update passes half
  its last place, so this is part of the stated semantics and not a
  rounding detail;
* gradient: clipped to global norm ``grad_clip_norm``;
* Adafactor (Shazeer & Stern 2018) as the program's library states
  it: second moments factored over a leaf's two largest axes when the
  smaller is >= 128, decay ``1 - t^-0.8``, eps 1e-30 on the squared
  gradient, update clipped to block RMS 1, scaled by the learning rate
  and by ``max(1e-3, RMS(parameter))``, no momentum, no weight decay;
* learning rate: linear warm-up from 0 over ``warmup_steps`` then
  cosine decay to 0 at ``total_steps``.

Departures: the gradient with respect to a bfloat16 leaf is returned in
bfloat16 (autodiff rounds the float32 cotangent once, 2^-9 relative,
far under what is compared), which keeps the reference's gradient tree
at half the size; rows are processed one at a time and the backward
pass is written out layer by layer (``_row_grads``), so that the
gradient tree is held once and the whole fits beside the weights.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import llama as ref

_F32 = jnp.float32


def lr_at(step: int, tcfg: Dict[str, Any]) -> float:
    """Learning rate of update number ``step`` (0-based)."""
    peak, warm = float(tcfg['learning_rate']), int(tcfg['warmup_steps'])
    total = max(int(tcfg['total_steps']), warm + 1)
    if step < warm:
        return peak * step / warm
    t = min(step - warm, total - warm)
    return peak * 0.5 * (1.0 + math.cos(math.pi * t / (total - warm)))


def factored_dims(shape) -> Optional[Tuple[int, int]]:
    """(d1, d0): the axes of the second-largest and the largest
    extent, when the second-largest is >= 128; else None."""
    if len(shape) < 2:
        return None
    order = np.argsort(shape)
    if shape[order[-2]] < 128:
        return None
    return int(order[-2]), int(order[-1])


def init_opt_state(params) -> Dict[str, Any]:
    def one(p):
        dims = factored_dims(p.shape)
        if dims is None:
            return {'v': jnp.zeros(p.shape, _F32)}
        d1, d0 = dims
        return {'v_row': jnp.zeros(np.delete(p.shape, d0), _F32),
                'v_col': jnp.zeros(np.delete(p.shape, d1), _F32)}
    return {'count': 0, 'v': jax.tree.map(one, params)}


@functools.partial(jax.jit, static_argnames=('decay', 'lr'),
                   donate_argnums=(1,))
def _leaf_update(p, g, st, clip_scale, decay: float, lr: float):
    """One leaf of Adafactor; returns (new bf16 parameter, new state)."""
    g = g.astype(_F32) * clip_scale
    p32 = p.astype(_F32)
    g2 = g * g + 1e-30
    dims = factored_dims(p.shape)
    if dims is None:
        v = decay * st['v'] + (1.0 - decay) * g2
        u = g * v ** -0.5
        new_st = {'v': v}
    else:
        d1, d0 = dims
        v_row = decay * st['v_row'] + (1.0 - decay) * jnp.mean(g2, axis=d0)
        v_col = decay * st['v_col'] + (1.0 - decay) * jnp.mean(g2, axis=d1)
        red = d1 - 1 if d1 > d0 else d1
        row_factor = (v_row / jnp.mean(v_row, axis=red, keepdims=True)) \
            ** -0.5
        u = (g * jnp.expand_dims(row_factor, d0)
             * jnp.expand_dims(v_col ** -0.5, d1))
        new_st = {'v_row': v_row, 'v_col': v_col}
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u)))
    u = u * lr * jnp.maximum(jnp.sqrt(jnp.mean(p32 * p32)), 1e-3)
    new_p = (p32 - u).astype(p.dtype)
    return new_p, new_st


def leaf_sq_norms(tree) -> Dict[str, float]:
    """{path: sum of squares} over the leaves of a parameter-shaped
    tree, on the host."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    # skylint: allow-jit(benchmark-side program: the reference and the
    # harness are outside the serving compile ledger by design)
    sq = jax.jit(lambda t: [jnp.sum(jnp.square(x.astype(_F32)))
                            for x in jax.tree.leaves(t)])(tree)
    return {jax.tree_util.keystr(path): float(v)
            for (path, _), v in zip(flat, sq)}


def train_step(params, opt, tokens, cfg: Dict[str, Any],
               tcfg: Dict[str, Any], quant: Optional[str] = None,
               grad_fn=None):
    """One step. Returns (new params, new opt state, loss, {leaf:
    gradient norm as the optimizer chain gets it (unclipped)},
    global gradient norm)."""
    if grad_fn is None:
        grad_fn = make_grad_fn(cfg, quant)
    loss, grads = grad_fn(params, jnp.asarray(tokens, jnp.int32))
    sq = leaf_sq_norms(grads)
    g_norm = math.sqrt(sum(sq.values()))
    clip = float(tcfg.get('grad_clip_norm', 1.0))
    clip_scale = 1.0 if g_norm < clip else clip / g_norm
    step = int(opt['count'])
    decay = 1.0 - (step + 1.0) ** -0.8
    lr = lr_at(step, tcfg)
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_s = treedef.flatten_up_to(opt['v'])
    del grads
    new_p, new_s = [], []
    for i in range(len(leaves_p)):
        p, g, st = leaves_p[i], leaves_g[i], leaves_s[i]
        leaves_g[i] = None
        np_, ns_ = _leaf_update(p, g, st, jnp.float32(clip_scale),
                                decay=decay, lr=lr)
        new_p.append(np_)
        new_s.append(ns_)
    new_opt = {'count': step + 1, 'v': treedef.unflatten(new_s)}
    return (treedef.unflatten(new_p), new_opt, float(loss),
            {k: math.sqrt(v) for k, v in sq.items()}, g_norm)


def _row_grads(params, row, acc, weight, cfg_items, quant):
    """Loss of ONE row and its gradient added into ``acc`` (donated),
    the backward pass written out layer by layer so that the gradient
    tree is only ever held once: the stacked leaves of ``acc`` are
    updated in place as the loop walks the layers in reverse.
    ``weight`` is the row's share of the batch mean."""
    cfg = dict(cfg_items)
    positions = jnp.arange(row.shape[0], dtype=jnp.int32)
    layers = params['layers']
    n_layers = jax.tree.leaves(layers)[0].shape[0]
    x0 = params['embed'][row].astype(_F32)

    def fwd(x, w):
        return ref.layer(x, w, positions, cfg, quant), x

    x_last, xs = jax.lax.scan(fwd, x0, layers)   # xs[l]: input of layer l

    def head_loss(x, norm_w, head):
        h = ref.rms_norm(x, norm_w.astype(_F32), cfg['rms_norm_eps'])[:-1]
        tgt = row[1:]
        n = row.shape[0] - 1
        blk = 1024
        pad = (-n) % blk
        h = jnp.pad(h, ((0, pad), (0, 0)))
        tgt = jnp.pad(tgt, (0, pad))
        head32 = head.astype(_F32)

        @jax.checkpoint
        def block_nll(a):
            logits = ref._mm(a[0], head32, 'td,dv->tv', quant)
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, a[1][:, None], axis=-1)[:, 0]
            return logz - gold

        nll = jax.lax.map(block_nll, (h.reshape(-1, blk, h.shape[-1]),
                                      tgt.reshape(-1, blk)))
        return jnp.sum(nll.reshape(-1)[:n]) / n

    loss, (dx, d_norm, d_head) = jax.value_and_grad(
        head_loss, argnums=(0, 1, 2))(x_last, params['final_norm'],
                                      params['lm_head'])
    dx = dx * weight

    def bwd(i, carry):
        dx, acc_layers = carry
        l = n_layers - 1 - i
        w_l = jax.tree.map(lambda a: a[l], layers)
        _, vjp = jax.vjp(
            lambda x, w: ref.layer(x, w, positions, cfg, quant), xs[l], w_l)
        dx, dw = vjp(dx)
        acc_layers = jax.tree.map(
            lambda a, g: a.at[l].add(g.astype(a.dtype)), acc_layers, dw)
        return dx, acc_layers

    dx, acc_layers = jax.lax.fori_loop(0, n_layers, bwd,
                                       (dx, acc['layers']))
    d_embed = jnp.zeros(params['embed'].shape, _F32).at[row].add(dx)
    new_acc = {
        'embed': acc['embed'] + d_embed.astype(acc['embed'].dtype),
        'layers': acc_layers,
        'final_norm': acc['final_norm']
        + (d_norm.astype(_F32) * weight).astype(acc['final_norm'].dtype),
        'lm_head': acc['lm_head']
        + (d_head.astype(_F32) * weight).astype(acc['lm_head'].dtype)}
    return loss, new_acc


def make_grad_fn(cfg: Dict[str, Any], quant: Optional[str] = None,
                 rows: Optional[slice] = None):
    """(params, tokens [B, S]) -> (mean loss, gradient tree), one row
    at a time. ``rows`` plants the half-batch fault in the reference
    (the mean is then over those rows only)."""
    items = ref.cfg_items(cfg)
    # skylint: allow-jit(benchmark-side program: the reference and the
    # harness are outside the serving compile ledger by design)
    row_fn = jax.jit(_row_grads, static_argnames=('cfg_items', 'quant'),
                     donate_argnums=(2,))
    # skylint: allow-jit(benchmark-side program: the reference and the
    # harness are outside the serving compile ledger by design)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))

    def f(params, tokens):
        if rows is not None:
            tokens = tokens[rows]
        b = tokens.shape[0]
        acc = zeros(params)
        total = 0.0
        for r in range(b):
            loss, acc = row_fn(params, tokens[r], acc, jnp.float32(1.0 / b),
                               cfg_items=items, quant=quant)
            total += float(loss)
        return total / b, acc
    return f


def change_sq_norms(new, old) -> Dict[str, float]:
    """{leaf: ||new - old||^2} in float32, on the host."""
    flat = jax.tree_util.tree_flatten_with_path(new)[0]
    # skylint: allow-jit(benchmark-side program: the reference and the
    # harness are outside the serving compile ledger by design)
    sq = jax.jit(lambda a, b: [
        jnp.sum(jnp.square(x.astype(_F32) - y.astype(_F32)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])(new, old)
    return {jax.tree_util.keystr(path): float(v)
            for (path, _), v in zip(flat, sq)}
