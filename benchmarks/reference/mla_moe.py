"""The plain reference of the ``mla_moe`` family (Xing4.0-29B-A4B is
this block): a decoder with a hyper-connected residual stream, latent
(MLA) attention and sigmoid-routed experts with a shared expert, in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision.
No kernels, no cache, no absorption, no sorting, no batching tricks. It
imports nothing of the program.

What is computed, for a token's residual ``X`` in R^{n x d}
(n = ``hc_mult``; the configuration file's ``assumed`` block says which
points the published config leaves open):

* **Residual stream** (mHC): ``X_0`` = the embedding repeated n times.
  A sub-layer F with its own maps ``phi`` [n*d, n*n + 2n], ``alpha``
  [3] = (pre, post, res), ``bias`` [n*n + 2n] = (pre | post | res):
  ``x~ = RMSNorm(vec(X))`` (no gain, eps ``hc_eps``), ``m = x~ phi``,
  ``H_pre = sigmoid(alpha_pre m[:n] + b_pre)``,
  ``H_post = 2 sigmoid(alpha_post m[n:2n] + b_post)``,
  ``H_res = Sinkhorn(exp(clip(alpha_res mat(m[2n:]) + b_res)))`` with
  ``hc_sinkhorn_iters`` rounds of "divide rows by their sum + eps, then
  columns"; ``X' = H_res X + H_post^T F(RMSNorm_g(H_pre X))``. After the
  last layer ``x = sum_rows X``, final RMSNorm, output head. With
  ``hc_mult`` 1 there are no maps: ``X' = X + F(RMSNorm_g(X))``.
* **MLA**: ``c_q = RMSNorm(x W_qa)``, ``[q_nope | q_rope] = c_q W_qb``
  a head; ``[c_kv | k_rope] = x W_kva``, ``c_kv <- RMSNorm(c_kv)``;
  rotary (rotate-half over the rope dims, YaRN frequencies, cos/sin
  unscaled because ``mscale == mscale_all_dim``) on ``q_rope`` and on
  the one shared ``k_rope``; ``[k_nope | v] = c_kv W_kvb`` a head,
  ``k = [k_nope | k_rope]``; causal softmax attention scaled by
  ``(nope + rope)^-1/2 (0.1 mscale_all_dim ln(factor) + 1)^2``; ``W_o``.
* **Experts**: ``s = sigmoid(x W_g)``; the ``num_experts_per_tok``
  experts with the largest ``s + b`` (``b`` selects, it does not
  weigh); ``w = routed_scaling_factor s_sel / (sum s_sel + 1e-20)``;
  ``y = sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x)``: EVERY token goes
  through a loop over the experts under a mask, nothing is dropped.
  Layers ``0 .. first_k_dense_replace - 1`` have a dense SwiGLU. With
  ``expert_range`` [lo, hi) in the configuration only those experts'
  part is added (the chip's share of a stated deployment; routing is
  still over all of them).

Departure: the weights arrive stacked by layer in the program's layout
and in bfloat16; a layer, ONE expert and one attention head at a time
are cast to float32, so the whole model never
exists in float32 (9.6 GB of bfloat16 weights at the cell's size).

``quant='int8'`` is the CONTROL: every matrix product the configuration
states in bfloat16 (projections, the two products inside attention,
feed-forwards, experts, output head) on int8 operands; the router and
the hyper-connection maps, which the configuration states in float32,
stay float32. It must come out as not correct.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _q8(x: jax.Array, axis) -> jax.Array:
    """Symmetric int8 fake-quantization along ``axis`` (the contracted
    one): the values an int8 product would see, kept in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, spec: str, quant: Optional[str], x_axis=-1, w_axes=(0,)):
    """``einsum(spec, x, w)`` at highest precision; under the control,
    on int8 operands (x per row over its contracted axis, w per output
    channel over its contracted axes)."""
    if quant == 'int8':
        x, w = _q8(x, x_axis), _q8(w, w_axes)
    elif quant is not None:
        raise ValueError(f'unknown control precision {quant!r}')
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return y if w is None else y * w


# -- rotary ------------------------------------------------------------------


def yarn_inv_freq(cfg: Dict[str, Any]) -> jax.Array:
    """Inverse frequencies of the rope dims [rope / 2]. Without
    ``rope_scaling`` the plain ``theta^(-2i/dim)``; with YaRN the
    published blend: dims that turn more than ``beta_fast`` times over
    the original context keep their frequency, those that turn fewer
    than ``beta_slow`` times are divided by ``factor``, a linear ramp
    between."""
    dim, base = cfg['qk_rope_head_dim'], float(cfg['rope_theta'])
    exps = jnp.arange(0, dim, 2, dtype=_F32) / dim
    extra = 1.0 / (base ** exps)
    rs = cfg.get('rope_scaling')
    if not rs:
        return extra
    factor, orig = float(rs['factor']), rs['original_max_position_embeddings']

    def corr_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(rs['beta_fast'])), 0)
    high = min(math.ceil(corr_dim(rs['beta_slow'])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_F32) - low) / (high - low),
                    0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def softmax_scale(cfg: Dict[str, Any]) -> float:
    scale = (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']) ** -0.5
    rs = cfg.get('rope_scaling')
    if rs and rs.get('mscale_all_dim'):
        m = 0.1 * rs['mscale_all_dim'] * math.log(rs['factor']) + 1.0
        scale *= m * m
    return scale


def rope(x, positions, inv_freq):
    """x [T, ..., D] with T leading, positions [T]; rotate-half."""
    half = x.shape[-1] // 2
    ang = positions.astype(_F32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- hyper-connections -------------------------------------------------------


def sinkhorn(m, iters: int, eps: float):
    """m [..., n, n] positive -> doubly stochastic, ``iters`` rounds."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_maps(xs, phi, alpha, bias, cfg):
    """xs [T, n, d] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    t, n, d = xs.shape
    flat = rms_norm(xs.reshape(t, n * d), None, cfg['hc_eps'])
    m = jnp.einsum('tk,kj->tj', flat, phi, precision=HIGHEST)
    pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + bias[n:2 * n])
    res = alpha[2] * m[:, 2 * n:] + bias[2 * n:]
    res = jnp.clip(res, cfg['mhc_h_res_clamp_min'],
                   cfg['mhc_h_res_clamp_max'])
    res = sinkhorn(jnp.exp(res).reshape(t, n, n), cfg['hc_sinkhorn_iters'],
                   cfg['hc_eps'])
    return pre, post, res


def sublayer(xs, w, prefix: str, norm: str, fn, cfg):
    """One hyper-connected sub-layer around ``fn`` ([T, d] -> [T, d])."""
    if cfg.get('hc_mult', 1) == 1:
        x = xs[:, 0]
        return (x + fn(rms_norm(x, w[norm], cfg['rms_norm_eps'])))[:, None]
    pre, post, res = hc_maps(xs, w[prefix + '_phi'], w[prefix + '_alpha'],
                             w[prefix + '_bias'], cfg)
    h = jnp.einsum('tn,tnd->td', pre, xs, precision=HIGHEST)
    y = fn(rms_norm(h, w[norm], cfg['rms_norm_eps']))
    return (jnp.einsum('tij,tjd->tid', res, xs, precision=HIGHEST)
            + post[:, :, None] * y[:, None, :])


# -- attention ---------------------------------------------------------------


def _attend_head(q, k, v, scale, quant):
    """One head, causal. q/k [T, Dk], v [T, Dv] -> [T, Dv]."""
    t = q.shape[0]
    if quant == 'int8':
        q, k = _q8(q, -1), _q8(k, -1)
    s = jnp.einsum('td,ud->tu', q, k, precision=HIGHEST) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant == 'int8':
        p, v = _q8(p, -1), _q8(v, 0)
    return jnp.einsum('tu,ud->td', p, v, precision=HIGHEST)


def mla(h, w, positions, cfg, quant):
    """h [T, d] -> [T, d]: latent attention, expanded."""
    eps = cfg['rms_norm_eps']
    nope, rdim = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    rank = cfg['kv_lora_rank']
    inv_freq = yarn_inv_freq(cfg)
    c_q = rms_norm(_mm(h, w['wq_a'], 'td,dr->tr', quant), w['q_norm'], eps)
    q = _mm(c_q, w['wq_b'], 'tr,rhk->thk', quant)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, inv_freq)], -1)
    kv = _mm(h, w['wkv_a'], 'td,dr->tr', quant)
    c_kv = rms_norm(kv[:, :rank], w['kv_norm'], eps)
    k_rope = rope(kv[:, rank:], positions, inv_freq)
    up = _mm(c_kv, w['wkv_b'], 'tr,rhk->thk', quant)
    k = jnp.concatenate(
        [up[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], up.shape[:2] + (rdim,))], -1)
    v = up[..., nope:]
    scale = softmax_scale(cfg)
    att = jax.lax.map(
        lambda a: _attend_head(a[0], a[1], a[2], scale, quant),
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return _mm(att.transpose(1, 0, 2), w['wo'], 'thk,hkd->td', quant,
               x_axis=(-2, -1), w_axes=(0, 1))


# -- feed-forward ------------------------------------------------------------


def swiglu(x, gate, up, down, quant):
    g = _mm(x, gate, 'td,df->tf', quant)
    u = _mm(x, up, 'td,df->tf', quant)
    return _mm(jax.nn.silu(g) * u, down, 'tf,fd->td', quant)


def route(x, router, bias, cfg):
    """x [T, d] -> combine weights [T, E] float32: ``w`` at a token's
    selected experts, 0 elsewhere."""
    k = cfg['num_experts_per_tok']
    s = jax.nn.sigmoid(jnp.einsum('td,de->te', x, router, precision=HIGHEST))
    _, idx = jax.lax.top_k(s + bias[None, :], k)
    hot = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=_F32), axis=1)
    sel = s * hot
    if cfg.get('norm_topk_prob', True):
        sel = sel / (jnp.sum(sel, axis=-1, keepdims=True) + 1e-20)
    return sel * cfg['routed_scaling_factor']


def experts(x, w, cfg, quant):
    """Routed experts under a mask plus the shared expert. The expert
    weights stay bfloat16 until their turn."""
    e = w['we_gate'].shape[0]
    lo, hi = cfg.get('expert_range') or (0, e)
    comb = route(x, w['router'].astype(_F32), w['router_bias'].astype(_F32),
                 cfg)
    held = (jnp.arange(e) >= lo) & (jnp.arange(e) < hi)
    comb = comb * held[None, :].astype(_F32)

    def one(acc, ws):
        gate, up, down, cw = ws          # one expert; cw [T]
        y = swiglu(x, gate.astype(_F32), up.astype(_F32), down.astype(_F32),
                   quant)
        return acc + cw[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (w['we_gate'], w['we_up'], w['we_down'], comb.T))
    if 'ws_gate' in w:
        out = out + swiglu(x, w['ws_gate'].astype(_F32),
                           w['ws_up'].astype(_F32),
                           w['ws_down'].astype(_F32), quant)
    return out


# -- the model ---------------------------------------------------------------

_EXPERT_LEAVES = ('we_gate', 'we_up', 'we_down', 'ws_gate', 'ws_up',
                  'ws_down', 'router', 'router_bias')


def layer(xs, w, positions, cfg, quant):
    """One block on one sequence. xs [T, n, d] float32; ``w`` one
    layer's leaves, bfloat16 (expert leaves are cast block by block)."""
    small = {k: (v if k in _EXPERT_LEAVES else v.astype(_F32))
             for k, v in w.items()}
    xs = sublayer(xs, small, 'hc_attn', 'attn_norm',
                  lambda h: mla(h, small, positions, cfg, quant), cfg)
    if 'we_gate' in w:
        ffn = lambda h: experts(h, small, cfg, quant)   # noqa: E731
    else:
        ffn = lambda h: swiglu(h, small['w_gate'], small['w_up'],  # noqa
                               small['w_down'], quant)
    return sublayer(xs, small, 'hc_mlp', 'mlp_norm', ffn, cfg)


def hidden(params, tokens, cfg, quant=None):
    """tokens [T] -> final-norm hidden states [T, d] float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    n = cfg.get('hc_mult', 1)
    x = params['embed'][tokens].astype(_F32)
    xs = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    for stack in ('dense', 'moe'):
        if stack in params:
            xs, _ = jax.lax.scan(
                lambda c, w: (layer(c, w, positions, cfg, quant), None),
                xs, params[stack])
    return rms_norm(jnp.sum(xs, axis=1), params['final_norm'].astype(_F32),
                    cfg['rms_norm_eps'])


def cfg_items(cfg: Dict[str, Any]) -> tuple:
    """The configuration's numbers (those of ``rope_scaling`` and the
    ``expert_range`` pair too) as a hashable, for jit's static
    arguments."""
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()
                                if isinstance(x, (int, float, str, bool))))
        return tuple(v) if isinstance(v, list) else v
    nested = ('rope_scaling', 'expert_range')
    return tuple(sorted(
        (k, freeze(v)) for k, v in cfg.items()
        if isinstance(v, (int, float, bool))
        or (k in nested and v is not None)))


def cfg_from_items(items: tuple) -> Dict[str, Any]:
    cfg = dict(items)
    if isinstance(cfg.get('rope_scaling'), tuple):
        cfg['rope_scaling'] = dict(cfg['rope_scaling'])
    return cfg


@functools.partial(jax.jit, static_argnames=('cfg_items', 'quant'))
def _logits_at(params, tokens, rows, cfg_items, quant):
    cfg = cfg_from_items(cfg_items)
    h = hidden(params, tokens, cfg, quant)[rows]
    return _mm(h, params['lm_head'].astype(_F32), 'td,dv->tv', quant)


def logits_at(params, tokens, rows, cfg: Dict[str, Any],
              quant: Optional[str] = None):
    """Logits [len(rows), V] of one sequence at positions ``rows``
    (row ``i`` predicts token ``i + 1``). ``tokens`` may be padded on
    the right: causality keeps the padding out of earlier rows."""
    return _logits_at(params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(rows, jnp.int32), cfg_items(cfg), quant)
