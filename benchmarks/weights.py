"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself (not ``llama.init_params``) so
that the program under test and the plain reference are handed the
same tree and neither is handed anything the other made. The tree has
the layout the program serves and trains (stacked by layer):
``embed [V, d]``, ``layers/{attn_norm, wq [L, d, Hq, D], wk, wv
[L, d, Hkv, D], wo [L, Hq, D, d], mlp_norm, w_gate, w_up [L, d, F],
w_down [L, F, d]}``, ``final_norm [d]``, ``lm_head [d, V]``; bfloat16.

Norm weights are 1 + N(0, 0.1²) rather than exactly 1, so that a
reference that dropped a norm's weight would not agree by accident.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

_LEAVES = ('embed', 'attn_norm', 'wq', 'wk', 'wv', 'wo', 'mlp_norm',
           'w_gate', 'w_up', 'w_down', 'final_norm', 'lm_head')


def shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    d, L = cfg['hidden_size'], cfg['num_hidden_layers']
    hq, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    hd = cfg.get('head_dim') or d // hq
    f, v = cfg['intermediate_size'], cfg['vocab_size']
    return {'embed': (v, d), 'attn_norm': (L, d), 'wq': (L, d, hq, hd),
            'wk': (L, d, hkv, hd), 'wv': (L, d, hkv, hd),
            'wo': (L, hq, hd, d), 'mlp_norm': (L, d), 'w_gate': (L, d, f),
            'w_up': (L, d, f), 'w_down': (L, f, d), 'final_norm': (d,),
            'lm_head': (d, v)}


def _fan_in(name: str, cfg: Dict[str, Any]) -> float:
    d = cfg['hidden_size']
    hd = cfg.get('head_dim') or d // cfg['num_attention_heads']
    return {'embed': 1.0, 'wo': cfg['num_attention_heads'] * hd,
            'w_down': cfg['intermediate_size']}.get(name, d)


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def _make(key: jax.Array, cfg_items: tuple) -> Dict[str, Any]:
    cfg = dict(cfg_items)
    out: Dict[str, Any] = {}
    for i, name in enumerate(_LEAVES):
        k = jax.random.fold_in(key, i)
        shape = shapes(cfg)[name]
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith('norm'):
            x = 1.0 + 0.1 * x
        else:
            x = x * (_fan_in(name, cfg) ** -0.5)
        out[name] = x.astype(jnp.bfloat16)
    layers = {n: out.pop(n) for n in list(out)
              if n not in ('embed', 'final_norm', 'lm_head')}
    return {'embed': out['embed'], 'layers': layers,
            'final_norm': out['final_norm'], 'lm_head': out['lm_head']}


def _hashable(cfg: Dict[str, Any]) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float)) and v is not None))


def make_params(cfg: Dict[str, Any], seed: int, shardings=None):
    """The whole tree in one jitted call; ``shardings`` (a matching tree
    of ``jax.sharding.Sharding``) makes each chip only its own shard."""
    # skylint: allow-jit(benchmark-side program: the reference and the
    # harness are outside the serving compile ledger by design)
    fn = jax.jit(_make, static_argnums=(1,), out_shardings=shardings)
    return fn(seed_key(seed), _hashable(cfg))


def param_count(cfg: Dict[str, Any]) -> int:
    n = 0
    for shape in shapes(cfg).values():
        c = 1
        for s in shape:
            c *= s
        n += c
    return n
