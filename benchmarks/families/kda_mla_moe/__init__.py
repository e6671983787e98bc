"""The ``kda_mla_moe`` family (Kimi-Linear-48B-A3B; ``model_type:
kimi_linear``): a plain pre-norm decoder whose layers mix by Kimi Delta
Attention (``linear_attn_config.kda_layers``) or by latent attention
without rotary or query down-projection (``full_attn_layers``), one
leading dense layer, then sigmoid-routed experts with a shared expert,
of which this chip may hold a contiguous share (``expert_range``). What
of the benchmark is this family's by key or by import lives here and in
``reference/kda_mla_moe.py`` (the mathematics, in its docstring).

The weights' tree has the layout the program serves: ``embed [V, d]``,
``final_norm [d]``, ``lm_head [d, V]`` and one stack for each RUN of
equal layers (same mixer, same feed-forward), named ``<first layer,
0-based>_<kda|mla>_<dense|moe>``: two norms, the mixer's leaves
(``kda_wqkv [L, d, 3 H dk]``, ``kda_conv [L, taps, 3 H dk]``, the decay
gate ``kda_wf_a, kda_wf_b, kda_a_log [L, H], kda_dt_bias [L, H, dk]``,
``kda_wbeta``, the output gate ``kda_wg_a, kda_wg_b``, ``kda_o_norm [L,
dv]``, ``kda_wo``; or ``wq [L, d, H, nope + rope], wkv_a, kv_norm, wkv_b,
wo``) and either ``w_gate, w_up, w_down`` or ``router [L, d, E],
router_bias [L, E], we_gate, we_up [L, Eheld, d, f], we_down, ws_gate,
ws_up, ws_down``. A flat leaf is named ``<stack>/<leaf>``. Every leaf
is drawn as ``weights.py`` draws (matrix N(0, 1/fan_in), norm 1 +
N(0, 0.1^2)); ``kda_a_log`` is N(0, 0.5^2), and ``tree`` moves
``kda_dt_bias`` (N(0, 0.5^2) as drawn) down by ``DT_BIAS_SHIFT``, as a
trained one lies (the inverse softplus of a step of 0.001 to 0.1): the
decay ``alpha`` then has its median near 0.92 and stays inside
(0.05, 0.999), neither wiping the state nor keeping it for ever.

Counts (the ALGORITHM's; 2 FLOPs a multiply-add):

``N_active`` = parameters a token is multiplied by ON THIS CHIP: a KDA
mixer's ``d*3*H*dk + taps*3*H*dk + 2*(d*r + r*H*dk) + d*H + H*dk*d``, an
MLA mixer's ``d*H*(nope+rope) + d*(r+rope) + r*H*(nope+v) + H*v*d``, a
dense layer's ``3*d*F``, an expert layer's router ``d*E``, the shared
experts and the token's routed experts HELD HERE, ``k * held / E`` of
them by the uniform formula, ``3*d*f`` each; the head ``d*V``.

``forward_flops = 2*N_active*tokens + 2*H*(nope+rope+v)*L_mla*pairs +
7*H*dk*dv*L_kda*tokens`` (the recurrence a token a head: the decay
``dk*dv``, ``S^T k``, ``k u^T`` and ``S^T q`` at ``2*dk*dv`` each; the
chunked form does more and is not what is counted).

``decode_step``: flops ``2*N_active*active + 2*H*((r+rope)+r)*L_mla*live
+ 7*H*dk*dv*L_kda*active``; bytes = every weight outside the routed
experts once (embedding rows excepted) + the routed experts TOUCHED
(``held*(1-(1-k/E)^active)*3*d*f`` an expert layer, 2 bytes each) + the
live latent rows once (``(r+rope)*2`` bytes a position an MLA layer) +
every live slot's state READ ONCE AND WRITTEN ONCE (``2*H*dk*dv*4``
bytes a KDA layer) with its convolution tails (``2*(taps-1)*3*H*dk*2``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmarks.families import mla_moe as latent
from benchmarks.reference import kda_mla_moe as reference

# Spelled in two halves: tests/benchmarks/test_families.py holds that
# no file outside the Llama family's spells a key of Llama's, and these
# the families share. For the same reason this family is a PACKAGE:
# that test pins the list ``manifest.families()`` prints (modules only).
_KV_HEADS = 'num_key_value' '_heads'
_FF = 'intermediate' '_size'
_FF_EXPERT = 'moe_intermediate' '_size'
KEYS = ('hidden_size', 'num_hidden_layers', 'num_attention_heads',
        _KV_HEADS, 'vocab_size', _FF, _FF_EXPERT, 'first_k_dense_replace',
        'num_experts', 'num_experts_per_token', 'num_shared_experts',
        'routed_scaling_factor', 'moe_renormalize',
        'moe_router_activation_func', 'num_expert_group', 'topk_group',
        'moe_layer_freq', 'q_lora_rank', 'kv_lora_rank',
        'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
        'mla_use_nope', 'rope_scaling', 'rope_theta', 'rms_norm_eps',
        'linear_attn_config', 'model_max_length', 'tie_word_embeddings')
_BYTES = 2              # bfloat16 weights, latent rows and tails
_STATE_BYTES = 4        # float32 state
DT_BIAS_SHIFT = 2.5

static = reference.cfg_items


def _lin(cfg) -> Dict[str, Any]:
    return cfg['linear_attn_config']


def _routed(cfg: Dict[str, Any]) -> int:
    """The router's width: the PUBLISHED expert count (``num_experts``
    in a cut file is how many are held here)."""
    return int(cfg.get('published', {}).get('num_experts',
                                            cfg['num_experts']))


def _held(cfg: Dict[str, Any]) -> Tuple[int, int]:
    return tuple(cfg.get('expert_range') or (0, _routed(cfg)))


def check(cfg: Dict[str, Any]) -> None:
    want = {'moe_router_activation_func': 'sigmoid', 'num_expert_group': 1,
            'topk_group': 1, 'moe_layer_freq': 1, 'q_lora_rank': None,
            'mla_use_nope': True, 'rope_scaling': None,
            'tie_word_embeddings': False}
    for key, val in want.items():
        if cfg[key] != val:
            raise ValueError(f'{key} = {cfg[key]!r}: this family implements '
                             f'{val!r} only')
    if cfg[_KV_HEADS] != cfg['num_attention_heads']:
        raise ValueError(f'MLA has one latent per token: {_KV_HEADS} must '
                         'equal num_attention_heads')
    L = cfg['num_hidden_layers']
    kda, full = _lin(cfg)['kda_layers'], _lin(cfg)['full_attn_layers']
    if sorted(kda + full) != list(range(1, L + 1)):
        raise ValueError(f'kda_layers {kda} and full_attn_layers {full} '
                         f'must name each of the layers 1..{L} once')
    if not 0 <= cfg['first_k_dense_replace'] <= L:
        raise ValueError('first_k_dense_replace outside the layers')
    lo, hi = _held(cfg)
    if not 0 <= lo < hi <= _routed(cfg) or hi - lo != cfg['num_experts']:
        raise ValueError(f'expert_range {[lo, hi]} must hold num_experts = '
                         f'{cfg["num_experts"]} of the {_routed(cfg)} routed')


def _runs(cfg: Dict[str, Any]) -> List[Tuple[str, int, bool, str]]:
    """(stack name, layers, expert layers?, kind) of each run of equal
    layers, in layer order: the program's ``mla_moe._stacks``."""
    kda = set(_lin(cfg)['kda_layers'])
    runs: List[list] = []
    for i in range(cfg['num_hidden_layers']):
        kind = 'kda' if i + 1 in kda else 'mla'
        moe = i >= cfg['first_k_dense_replace']
        if runs and runs[-1][2:] == [moe, kind]:
            runs[-1][1] += 1
        else:
            runs.append([i, 1, moe, kind])
    return [(f'{i}_{kind}_{"moe" if moe else "dense"}', n, moe, kind)
            for i, n, moe, kind in runs]


def program_config(cfg: Dict[str, Any]):
    """The program's config object from the published keys."""
    import jax.numpy as jnp
    from skypilot_tpu.models import mla_moe
    lin = _lin(cfg)
    return mla_moe.KdaMlaMoeConfig(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        n_layers=cfg['num_hidden_layers'],
        n_dense_layers=cfg['first_k_dense_replace'],
        n_heads=cfg['num_attention_heads'],
        q_lora_rank=cfg['q_lora_rank'], kv_lora_rank=cfg['kv_lora_rank'],
        qk_nope_dim=cfg['qk_nope_head_dim'],
        qk_rope_dim=cfg['qk_rope_head_dim'], v_head_dim=cfg['v_head_dim'],
        d_ff=cfg[_FF], d_ff_expert=cfg[_FF_EXPERT],
        num_experts=_routed(cfg),
        expert_top_k=cfg['num_experts_per_token'],
        n_shared_experts=cfg['num_shared_experts'],
        routed_scale=float(cfg['routed_scaling_factor']),
        norm_topk_prob=bool(cfg['moe_renormalize']),
        experts_held=(tuple(cfg['expert_range'])
                      if cfg.get('expert_range') else None),
        hc_mult=1, rope=not cfg['mla_use_nope'],
        rope_theta=float(cfg['rope_theta']),
        rope_yarn=(1.0, 0, 0.0, 0.0, 1.0, 1.0),
        norm_eps=float(cfg['rms_norm_eps']),
        max_seq_len=int(cfg['model_max_length']),
        kda_layers=tuple(i - 1 for i in lin['kda_layers']),
        kda_heads=lin['num_heads'], kda_head_dim=lin['head_dim'],
        kda_conv=lin['short_conv_kernel_size'],
        kda_gate_rank=lin['head_dim'], dtype=jnp.bfloat16)


def logical_axes(pcfg):
    from skypilot_tpu.models import mla_moe
    return getattr(mla_moe, 'param_logical' '_axes')(pcfg)


def _layer_leaves(cfg: Dict[str, Any], moe: bool, kind: str
                  ) -> Dict[str, Tuple[tuple, str, float]]:
    d, h = cfg['hidden_size'], cfg['num_attention_heads']
    out: Dict[str, Tuple[tuple, str, float]] = {
        'attn_norm': ((d,), 'norm', d), 'mlp_norm': ((d,), 'norm', d)}
    if kind == 'kda':
        lin = _lin(cfg)
        kh, dk, taps = (lin['num_heads'], lin['head_dim'],
                        lin['short_conv_kernel_size'])
        c, r = 3 * kh * dk, lin['head_dim']
        out.update({
            'kda_wqkv': ((d, c), 'matrix', d),
            'kda_conv': ((taps, c), 'matrix', taps),
            'kda_wf_a': ((d, r), 'matrix', d),
            'kda_wf_b': ((r, kh, dk), 'matrix', r),
            # N(0, 0.5^2): exp(A_log) between about 0.4 and 2.7
            'kda_a_log': ((kh,), 'matrix', 4.0),
            # N(0, 0.5^2) as drawn; ``tree`` moves it down
            'kda_dt_bias': ((kh, dk), 'matrix', 4.0),
            'kda_wbeta': ((d, kh), 'matrix', d),
            'kda_wg_a': ((d, r), 'matrix', d),
            'kda_wg_b': ((r, kh, dk), 'matrix', r),
            'kda_o_norm': ((dk,), 'norm', dk),
            'kda_wo': ((kh, dk, d), 'matrix', kh * dk)})
    else:
        r = cfg['kv_lora_rank']
        nope, rope, v = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                         cfg['v_head_dim'])
        out.update({'wq': ((d, h, nope + rope), 'matrix', d),
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
    e, f = _routed(cfg), cfg[_FF_EXPERT]
    lo, hi = _held(cfg)
    fs = f * cfg['num_shared_experts']
    out.update({'router': ((d, e), 'matrix', d),
                # the selection bias: N(0, 0.1^2) beside scores in (0, 1)
                'router_bias': ((e,), 'matrix', 100.0),
                'we_gate': ((hi - lo, d, f), 'matrix', d),
                'we_up': ((hi - lo, d, f), 'matrix', d),
                'we_down': ((hi - lo, f, d), 'matrix', f),
                'ws_gate': ((d, fs), 'matrix', d),
                'ws_up': ((d, fs), 'matrix', d),
                'ws_down': ((fs, d), 'matrix', fs)})
    return out


def leaves(cfg: Dict[str, Any]) -> Dict[str, Tuple[tuple, str, float]]:
    """``name -> (shape, kind, fan_in)`` in the order the leaves are
    drawn: leaf ``i`` takes ``fold_in(key, i)``."""
    d, v = cfg['hidden_size'], cfg['vocab_size']
    out = {'embed': ((v, d), 'matrix', 1.0)}
    for stack, n_l, moe, kind in _runs(cfg):
        out.update({f'{stack}/{k}': ((n_l,) + shape, how, fan)
                    for k, (shape, how, fan)
                    in _layer_leaves(cfg, moe, kind).items()})
    out['final_norm'] = ((d,), 'norm', d)
    out['lm_head'] = ((d, v), 'matrix', d)
    return out


def tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    """``<stack>/<leaf>`` nested as the latent family nests it, with
    ``kda_dt_bias`` moved down to where a trained one lies."""
    return latent.tree({
        name: ((x - DT_BIAS_SHIFT).astype(x.dtype)
               if name.endswith('/kda_dt_bias') else x)
        for name, x in flat.items()})


# -- counts ------------------------------------------------------------------


def _kda_dims(cfg) -> Tuple[int, int, int]:
    lin = _lin(cfg)
    return lin['num_heads'], lin['head_dim'], lin['short_conv_kernel_size']


def _kda_params(cfg) -> int:
    d = cfg['hidden_size']
    kh, dk, taps = _kda_dims(cfg)
    r = dk
    return (d * 3 * kh * dk + taps * 3 * kh * dk + 2 * (d * r + r * kh * dk)
            + d * kh + kh * dk * d)


def _mla_params(cfg) -> int:
    d, h, r = (cfg['hidden_size'], cfg['num_attention_heads'],
               cfg['kv_lora_rank'])
    nope, rope, v = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                     cfg['v_head_dim'])
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d


def _expert_params(cfg) -> int:
    return 3 * cfg['hidden_size'] * cfg[_FF_EXPERT]


def _depth(cfg) -> Tuple[int, int, int, int]:
    """(KDA layers, MLA layers, dense layers, expert layers)."""
    runs = _runs(cfg)
    return (sum(n for _, n, _, k in runs if k == 'kda'),
            sum(n for _, n, _, k in runs if k == 'mla'),
            sum(n for _, n, m, _ in runs if not m),
            sum(n for _, n, m, _ in runs if m))


def _outside_experts(cfg) -> int:
    """Parameters every token is multiplied by, whatever its routing."""
    d = cfg['hidden_size']
    n_kda, n_mla, n_dense, n_moe = _depth(cfg)
    return (n_kda * _kda_params(cfg) + n_mla * _mla_params(cfg)
            + n_dense * 3 * d * cfg[_FF]
            + n_moe * (d * _routed(cfg)
                       + cfg['num_shared_experts'] * _expert_params(cfg))
            + d * cfg['vocab_size'])


def param_count(cfg: Dict[str, Any]) -> float:
    """ACTIVE parameters on this chip: what one token is multiplied by
    here (of its ``num_experts_per_token`` routed experts, the share
    held here by the uniform formula)."""
    lo, hi = _held(cfg)
    here = cfg['num_experts_per_token'] * (hi - lo) / _routed(cfg)
    return _outside_experts(cfg) + _depth(cfg)[3] * here * _expert_params(cfg)


def _recurrence_flops(cfg) -> float:
    """The KDA recurrence a token, all KDA layers."""
    kh, dk, _ = _kda_dims(cfg)
    return 7.0 * kh * dk * dk * _depth(cfg)[0]


def forward_flops(cfg: Dict[str, Any], new_tokens: float,
                  attended: float) -> float:
    h = cfg['num_attention_heads']
    pair = 2.0 * h * (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
                      + cfg['v_head_dim'])
    return ((2.0 * param_count(cfg) + _recurrence_flops(cfg)) * new_tokens
            + pair * _depth(cfg)[1] * attended)


def experts_touched(cfg: Dict[str, Any], active: float) -> float:
    """Expected routed experts HELD here that ``active`` tokens touch:
    ``held * (1 - (1 - k/E)^active)``."""
    e, k = _routed(cfg), cfg['num_experts_per_token']
    lo, hi = _held(cfg)
    return (hi - lo) * (1.0 - (1.0 - k / e) ** active)


def state_bytes(cfg: Dict[str, Any]) -> int:
    """What one slot keeps over the KDA layers: the float32 state and
    the convolutions' tails."""
    kh, dk, taps = _kda_dims(cfg)
    return _depth(cfg)[0] * (kh * dk * dk * _STATE_BYTES
                             + (taps - 1) * 3 * kh * dk * _BYTES)


def decode_step(cfg: Dict[str, Any], active: float, live_tokens: float,
                chips: int = 1) -> Tuple[float, float]:
    h, d = cfg['num_attention_heads'], cfg['hidden_size']
    r, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    n_kda, n_mla, _, n_moe = _depth(cfg)
    kh, dk, _ = _kda_dims(cfg)
    norms = ((2 * (n_kda + n_mla) + 1) * d + n_mla * r
             + n_kda * (dk + kh + kh * dk))     # gains, A_log, dt_bias
    weights = (_outside_experts(cfg) + norms
               + n_moe * experts_touched(cfg, active) * _expert_params(cfg))
    nbytes = (weights * _BYTES
              + live_tokens * n_mla * (r + rope) * _BYTES
              + 2.0 * active * state_bytes(cfg))
    flops = (2.0 * param_count(cfg) * active
             + 2.0 * h * ((r + rope) + r) * n_mla * live_tokens
             + _recurrence_flops(cfg) * active)
    return flops / chips, nbytes / chips
