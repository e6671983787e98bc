"""The plain reference of the ``kda_mla_moe`` family (Kimi-Linear-48B-
A3B is this block): a pre-norm decoder whose layers mix either by Kimi
Delta Attention (KDA, a gated delta-rule recurrence over a float32
state) or by latent attention (MLA) WITHOUT rotary and without a query
down-projection, and feed forward through a dense SwiGLU (the leading
layers) or sigmoid-routed experts with a shared expert. Straightforward
``jax.numpy``, float32, ``highest`` matmul precision. KDA is THE
RECURRENCE, token by token (``lax.scan`` over positions): no chunks, no
cache, no kernels. It imports nothing of the program; the router, the
SwiGLU, the softmax attention of one head and the int8 control's
rounding are ``reference/mla_moe.py``'s, where the equations are the
same.

Every layer: ``x <- x + Mixer(RMSNorm(x))``, ``x <- x + FFN(RMSNorm(x))``;
final RMSNorm, untied head.

* **KDA** (layers in ``linear_attn_config.kda_layers``, 1-based; H heads
  of dk = dv = ``linear_attn_config.head_dim``): ``[q~ | k~ | v~] = x
  W_qkv``; each channel through a causal depthwise convolution of
  ``short_conv_kernel_size`` taps (the LAST tap on the current token),
  then SiLU; q, k L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``),
  ``q <- q dk^-1/2``; ``g = -exp(A_log_h) softplus((x W_f_a) W_f_b +
  dt_bias)`` a channel; ``beta = sigmoid(x W_beta)`` a head;
  ``S <- Diag(exp g) S``, ``u = beta (v - S^T k)``, ``S <- S + k u^T``,
  ``o = S^T q`` from S = 0; ``y = (RMSNorm_head(o) * sigmoid((x W_g_a)
  W_g_b)) W_o`` with one learned gain over a head's dv.
* **MLA** (layers in ``full_attn_layers``): ``q = x W_q`` a head,
  ``[c | k_pe] = x W_kva``, ``c <- RMSNorm(c)``, ``[k_nope | v] = c
  W_kvb`` a head, ``k = [k_nope | k_pe]`` with the one ``k_pe`` shared by
  the heads, nothing rotated; causal softmax at ``(nope + rope)^-1/2``.
* **Experts**: ``reference/mla_moe.route`` over ALL the router's
  experts (sigmoid scores in float32, the ``num_experts_per_token``
  largest ``s + b`` selected, ``w = routed_scaling_factor s_sel / sum
  s_sel``), then a loop over the experts HELD here (``expert_range``
  [lo, hi): the weights' tree holds exactly those) under the mask of
  their columns, plus the shared expert. What the absent experts would
  add is left out: that is another chip's part of the sum.

``quant='int8'`` is the CONTROL: every matrix product the
configuration states in bfloat16 on int8 operands; the router, the
decay, beta and the state's recurrence, which it states in float32,
stay float32. It must come out as not correct. (The state and its
decay kept in bfloat16 were read once as a second control, PR 33: the
picks moved LESS than under the program's own bfloat16 activations,
``gap_mean`` 0.0074 against 0.0117, so no limit on ``gap_mean`` can
tell it; the control went, and ``tests/test_kda.py`` holds the
program's state, decay and beta to float32 instead. PERF.md, section 7.)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference import mla_moe as shared

_F32 = jnp.float32
_mm, rms_norm = shared._mm, shared.rms_norm
L2_EPS = 1e-6


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda(h, w, cfg, quant):
    """h [T, d] -> [T, d]: the recurrence, one token at a time."""
    t = h.shape[0]
    heads, dk = cfg['kda_heads'], cfg['kda_head_dim']
    taps = w['kda_conv'].shape[0]
    x = _mm(h, w['kda_wqkv'], 'td,dc->tc', quant)
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), _F32), x])
    y = sum(padded[i:i + t] * w['kda_conv'][i] for i in range(taps))
    q, k, v = (p.reshape(t, heads, dk)
               for p in jnp.split(jax.nn.silu(y), 3, axis=-1))
    q, k = _l2(q) * dk ** -0.5, _l2(k)
    f = _mm(_mm(h, w['kda_wf_a'], 'td,dr->tr', quant), w['kda_wf_b'],
            'tr,rhk->thk', quant)
    g = -jnp.exp(w['kda_a_log'])[:, None] * jax.nn.softplus(
        f + w['kda_dt_bias'])
    beta = jax.nn.sigmoid(_mm(h, w['kda_wbeta'], 'td,dh->th', None))

    def one(s, step):
        q, k, v, g, beta = step                   # [H, dk] ...; beta [H]
        s = s * jnp.exp(g)[..., None]
        u = beta[:, None] * (v - jnp.sum(s * k[..., None], 1))
        s = s + k[..., None] * u[:, None, :]
        return s, jnp.sum(s * q[..., None], 1)

    _, o = jax.lax.scan(one, jnp.zeros((heads, dk, dk), _F32),
                        (q, k, v, g, beta))
    gate = _mm(_mm(h, w['kda_wg_a'], 'td,dr->tr', quant), w['kda_wg_b'],
               'tr,rhk->thk', quant)
    o = rms_norm(o, w['kda_o_norm'], cfg['rms_norm_eps']) * jax.nn.sigmoid(
        gate)
    return _mm(o, w['kda_wo'], 'thk,hkd->td', quant, x_axis=(-2, -1),
               w_axes=(0, 1))


def mla_nope(h, w, cfg, quant):
    """h [T, d] -> [T, d]: latent attention, expanded, nothing
    rotated."""
    nope, rank = cfg['qk_nope_head_dim'], cfg['kv_lora_rank']
    q = _mm(h, w['wq'], 'td,dhk->thk', quant)
    kv = _mm(h, w['wkv_a'], 'td,dr->tr', quant)
    c = rms_norm(kv[:, :rank], w['kv_norm'], cfg['rms_norm_eps'])
    up = _mm(c, w['wkv_b'], 'tr,rhk->thk', quant)
    k_pe = jnp.broadcast_to(kv[:, None, rank:],
                            up.shape[:2] + (kv.shape[1] - rank,))
    k = jnp.concatenate([up[..., :nope], k_pe], -1)
    scale = (nope + cfg['qk_rope_head_dim']) ** -0.5
    att = jax.lax.map(
        lambda a: shared._attend_head(a[0], a[1], a[2], scale, quant),
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
         up[..., nope:].transpose(1, 0, 2)))
    return _mm(att.transpose(1, 0, 2), w['wo'], 'thk,hkd->td', quant,
               x_axis=(-2, -1), w_axes=(0, 1))


def experts(x, w, cfg, quant):
    """x [T, d] -> [T, d]: the held experts' part of the routed sum and
    the shared expert. The experts' weights stay bfloat16 until their
    turn."""
    router = w['router'].astype(_F32)
    lo, hi = cfg.get('expert_range') or (0, router.shape[-1])
    comb = shared.route(x, router, w['router_bias'].astype(_F32),
                        cfg)[:, lo:hi]

    def one(acc, ws):
        gate, up, down, cw = ws          # one expert; cw [T]
        y = shared.swiglu(x, gate.astype(_F32), up.astype(_F32),
                          down.astype(_F32), quant)
        return acc + cw[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (w['we_gate'], w['we_up'], w['we_down'], comb.T))
    return out + shared.swiglu(x, w['ws_gate'].astype(_F32),
                               w['ws_up'].astype(_F32),
                               w['ws_down'].astype(_F32), quant)


def layer(x, w, cfg, quant):
    """One block on one sequence. x [T, d] float32; ``w`` one layer's
    leaves, bfloat16 (an expert's are cast at its turn)."""
    small = {k: (v if k in shared._EXPERT_LEAVES else v.astype(_F32))
             for k, v in w.items()}
    h = rms_norm(x, small['attn_norm'], cfg['rms_norm_eps'])
    if 'kda_wqkv' in w:
        x = x + kda(h, small, cfg, quant)
    else:
        x = x + mla_nope(h, small, cfg, quant)
    h = rms_norm(x, small['mlp_norm'], cfg['rms_norm_eps'])
    if 'we_gate' in w:
        return x + experts(h, small, cfg, quant)
    return x + shared.swiglu(h, small['w_gate'], small['w_up'],
                             small['w_down'], quant)


def hidden(params, tokens, cfg, quant=None):
    """tokens [T] -> final-norm hidden states [T, d] float32. The
    stacks are the tree's runs of equal layers, in layer order (their
    names start with the run's first layer)."""
    x = params['embed'][tokens].astype(_F32)
    runs = sorted((k for k in params if k[0].isdigit()),
                  key=lambda k: int(k.split('_')[0]))
    for run in runs:
        x, _ = jax.lax.scan(
            lambda c, w: (layer(c, w, cfg, quant), None),
            x, params[run])
    return rms_norm(x, params['final_norm'].astype(_F32),
                    cfg['rms_norm_eps'])


def cfg_items(cfg: Dict[str, Any]) -> tuple:
    """What of the configuration the reference reads, under the names
    ``reference/mla_moe.py``'s functions know, as a hashable for jit's
    static arguments."""
    lin = cfg['linear_attn_config']
    out = {
        'rms_norm_eps': cfg['rms_norm_eps'],
        'kda_heads': lin['num_heads'], 'kda_head_dim': lin['head_dim'],
        'kv_lora_rank': cfg['kv_lora_rank'],
        'qk_nope_head_dim': cfg['qk_nope_head_dim'],
        'qk_rope_head_dim': cfg['qk_rope_head_dim'],
        'num_experts_per_tok': cfg['num_experts_per_token'],
        'norm_topk_prob': bool(cfg['moe_renormalize']),
        'routed_scaling_factor': cfg['routed_scaling_factor']}
    if cfg.get('expert_range'):
        out['expert_range'] = tuple(cfg['expert_range'])
    return tuple(sorted(out.items()))


@functools.partial(jax.jit, static_argnames=('cfg_items', 'quant'))
def _logits_at(params, tokens, rows, cfg_items, quant):
    h = hidden(params, tokens, dict(cfg_items), quant)[rows]
    return _mm(h, params['lm_head'].astype(_F32), 'td,dv->tv', quant)


def logits_at(params, tokens, rows, cfg: Dict[str, Any],
              quant: Optional[str] = None):
    """Logits [len(rows), V] of one sequence at positions ``rows``
    (row ``i`` predicts token ``i + 1``). ``tokens`` may be padded on
    the right: the recurrence and the attention are causal, so the
    padding stays out of earlier rows."""
    return _logits_at(params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(rows, jnp.int32), cfg_items(cfg), quant)
