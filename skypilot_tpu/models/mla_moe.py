"""Latent-attention (MLA) decoder with drop-free routed experts and a
hyper-connected residual stream, on the serving path (Xing4.0-29B-A4B
and DeepSeek-V3-shaped models are numbers of ``MlaMoeConfig``).

What differs from ``models/llama.py``'s block, and where it lives:

* **Residual stream of ``hc_mult`` rows a token** (mHC). Each sub-layer
  reads ``H_pre X``, and writes ``H_res X + H_post^T F(.)`` with maps
  computed from the stream itself (``_hc_maps``; ``H_res`` doubly
  stochastic by ``hc_sinkhorn_iters`` UNROLLED Sinkhorn rounds in
  float32, which XLA fuses into one loop fusion). ``hc_mult == 1`` has
  no maps: the plain pre-norm block.
* **A leading run of dense layers, then expert layers**: two stacked
  parameter trees (``dense``, ``moe``), one ``lax.scan`` each.
* **Experts**: ``models/moe.dropfree_mlp`` (sigmoid scores, selection
  bias, top-k re-normalised and scaled, no drops, grouped matmuls over
  the experts HELD here, a shared expert). Rows do not couple.
* **Latent cache**: ``c_kv | k_rope`` a token a layer (576 numbers for
  512 + 64; stored as rows of ``decode_attention.latent_width`` = 640,
  the tail zero: the HBM tiling pads to that anyway). ONE plane
  (``PagedKVCache.k`` / ``KVCache.k`` with ``v=None``), one "KV head".
* **Two attention paths over it, the same function**: EXPANDED
  (``_expand``: up-project the cached rows to per-head K and V) for a
  fresh prefill (flash kernel; V zero-padded to the K width) and for
  the shared-prefix prefill (masked softmax over the row's view, in
  blocks of queries); ABSORBED (``_absorb``: ``q_nope W_UK^T | q_rope``
  against the cached rows, values the rows' first ``kv_lora_rank``
  columns, then ``W_UV``) for the decode step, read through the block
  table, by length, once (``ops/decode_attention.mla_decode``).

The pool rides the layer scans as a CARRY and the kernel takes the
whole pool plus a layer index: nothing slices a plane out of it, so the
step makes no copy of the pool.

**A per-layer kind** (``KdaMlaMoeConfig``; Kimi-Linear-48B-A3B is its
numbers): a layer's mixer is latent attention (``'mla'``) or Kimi Delta
Attention (``'kda'``, ``models/kda.py``), by the published list. The
parameters are stacked by RUN of equal layers, one ``lax.scan`` a run,
and two kinds of cache ride the scans side by side: the latent pool,
with a plane for each MLA layer only, and a float32 state
``[L_kda, rows, H, dk, dv]`` with the convolutions' tails
``[L_kda, rows, cw - 1, 3 H dk]``, a row a SLOT whatever its length
(``LatentStatePool`` / ``StateKVCache``). Both are written in place at
the layer's index among the layers of its kind. Such a model's MLA may
have no query down-projection (``q_lora_rank`` None) and no rotary
(``rope`` False).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import kda, moe, paged, sampling
from skypilot_tpu.models.generate import KVCache
from skypilot_tpu.models.llama import rms_norm
from skypilot_tpu.models.paged import PagedKVCache, pool_view, pool_write
from skypilot_tpu.observability.profiler import profiled_jit
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import decode_attention

Params = Dict[str, Any]
_NEG_INF = -1e30
# Queries of a masked (non-flash) attention are taken this many at a
# time: [H, 512, max_len] float32 logits are 268 MB at 32 x 4096.
_QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 131_072
    d_model: int = 3584
    n_layers: int = 40
    n_dense_layers: int = 2         # leading layers with a dense SwiGLU
    n_heads: int = 32
    q_lora_rank: Optional[int] = 768    # None: q straight from x
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 9216                # dense layers
    d_ff_expert: int = 1024
    num_experts: int = 64           # 0: every layer dense
    expert_top_k: int = 4
    n_shared_experts: int = 1
    routed_scale: float = 2.0
    norm_topk_prob: bool = True
    # The contiguous range of experts held HERE (None = all): routing is
    # over all ``num_experts``, only these experts' part is added.
    experts_held: Optional[Tuple[int, int]] = None
    hc_mult: int = 4                # 1: plain residual, no maps
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    rope: bool = True               # False: nothing is rotated (NoPE)
    rope_theta: float = 10_000.0
    # YaRN (factor 1 = plain rotary): (factor, original length,
    # beta_fast, beta_slow, mscale, mscale_all_dim)
    rope_yarn: Tuple[float, int, float, float, float, float] = (
        64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    norm_eps: float = 1e-6
    max_seq_len: int = 262_144
    dtype: Any = jnp.bfloat16

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers if self.num_experts else 0

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer: ``'mla'`` here, every one."""
        return ('mla',) * self.n_layers

    def n_kind(self, kind: str) -> int:
        return self.kinds.count(kind)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_width(self) -> int:
        return decode_attention.latent_width(self.kv_lora_rank,
                                             self.qk_rope_dim)

    @property
    def kv_bytes_per_token(self) -> int:
        """What the cache must hold a token: ``latent_dim`` numbers a
        latent-attention layer."""
        return (self.n_kind('mla') * self.latent_dim
                * jnp.dtype(self.dtype).itemsize)

    def __post_init__(self):
        if not self.num_experts and self.n_dense_layers != self.n_layers:
            raise ValueError('without experts every layer is dense: '
                             f'n_dense_layers {self.n_dense_layers} != '
                             f'n_layers {self.n_layers}')


@dataclasses.dataclass(frozen=True)
class KdaMlaMoeConfig(MlaMoeConfig):
    """``MlaMoeConfig`` with a per-layer kind: the layers in
    ``kda_layers`` (0-based) mix by Kimi Delta Attention, the others by
    latent attention. Its own TYPE because its cache is two caches
    (``model_ops``)."""
    kda_layers: Tuple[int, ...] = ()
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_gate_rank: int = 128        # of the decay's and the output gate's

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple('kda' if i in self.kda_layers else 'mla'
                     for i in range(self.n_layers))

    @property
    def state_bytes_per_slot(self) -> int:
        """What the cache must hold a SEQUENCE, whatever its length: a
        state and the convolutions' tails a KDA layer."""
        return self.n_kind('kda') * kda.state_bytes_per_row(self)

    def __post_init__(self):
        super().__post_init__()
        if not all(0 <= i < self.n_layers for i in self.kda_layers):
            raise ValueError(f'kda_layers {self.kda_layers} outside the '
                             f'{self.n_layers} layers')


TINY = MlaMoeConfig(
    vocab_size=256, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16, d_ff=128, d_ff_expert=32, num_experts=8, expert_top_k=2,
    rope_yarn=(4.0, 64, 32.0, 1.0, 1.0, 1.0), max_seq_len=512)


# -- params -----------------------------------------------------------------


def _layer_shapes(cfg: MlaMoeConfig, moe_layer: bool, kind: str = 'mla'
                  ) -> Dict[str, Tuple[tuple, Any, float]]:
    """``name -> (shape, logical axes, fan_in)`` of one layer's leaves
    (fan_in 0 = a norm's weight; the stacked trees add a leading
    'layers' dim)."""
    d, h, n = cfg.d_model, cfg.n_heads, cfg.hc_mult
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    out: Dict[str, Tuple[tuple, Any, float]] = {}
    for sub in ('attn', 'mlp'):
        if n > 1:
            m = n * n + 2 * n
            out[f'hc_{sub}_phi'] = ((n * d, m), (None, None), n * d)
            out[f'hc_{sub}_alpha'] = ((3,), (None,), 1.0)
            out[f'hc_{sub}_bias'] = ((m,), (None,), 1.0)
        out[f'{sub}_norm'] = ((d,), (None,), 0)
    if kind == 'kda':
        out.update(kda.layer_shapes(cfg))
    else:
        if cfg.q_lora_rank:
            out.update({
                'wq_a': ((d, cfg.q_lora_rank), ('embed', None), d),
                'q_norm': ((cfg.q_lora_rank,), (None,), 0),
                'wq_b': ((cfg.q_lora_rank, h, qk),
                         (None, 'heads', 'head_dim'), cfg.q_lora_rank)})
        else:
            out['wq'] = ((d, h, qk), ('embed', 'heads', 'head_dim'), d)
        out.update({
            'wkv_a': ((d, cfg.latent_dim), ('embed', None), d),
            'kv_norm': ((cfg.kv_lora_rank,), (None,), 0),
            'wkv_b': ((cfg.kv_lora_rank, h,
                       cfg.qk_nope_dim + cfg.v_head_dim),
                      (None, 'heads', 'head_dim'), cfg.kv_lora_rank),
            'wo': ((h, cfg.v_head_dim, d), ('heads', 'head_dim', 'embed'),
                   h * cfg.v_head_dim)})
    if not moe_layer:
        f = cfg.d_ff
        out.update({'w_gate': ((d, f), ('embed', 'mlp'), d),
                    'w_up': ((d, f), ('embed', 'mlp'), d),
                    'w_down': ((f, d), ('mlp', 'embed'), f)})
        return out
    e, f = cfg.num_experts, cfg.d_ff_expert
    lo, hi = cfg.held
    out.update({
        'router': ((d, e), ('embed', None), d),
        'router_bias': ((e,), (None,), 100.0),
        'we_gate': ((hi - lo, d, f), ('expert', 'embed', 'mlp'), d),
        'we_up': ((hi - lo, d, f), ('expert', 'embed', 'mlp'), d),
        'we_down': ((hi - lo, f, d), ('expert', 'mlp', 'embed'), f)})
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        out.update({'ws_gate': ((d, fs), ('embed', 'mlp'), d),
                    'ws_up': ((d, fs), ('embed', 'mlp'), d),
                    'ws_down': ((fs, d), ('mlp', 'embed'), fs)})
    return out


def _stacks(cfg: MlaMoeConfig):
    """(name, layers, is-expert-stack, kind) of the stacks the model
    has, in layer order: one for each RUN of layers with the same
    mixer and the same feed-forward, so that each run is one scan. A
    model of one kind has the two it always had (``dense``, ``moe``);
    otherwise a run is named ``<first layer>_<kind>_<dense|moe>``."""
    is_moe = [bool(cfg.num_experts) and i >= cfg.n_dense_layers
              for i in range(cfg.n_layers)]
    runs = []
    for i, (kind, m) in enumerate(zip(cfg.kinds, is_moe)):
        if runs and runs[-1][2:] == [m, kind]:
            runs[-1][1] += 1
        else:
            runs.append([i, 1, m, kind])
    if set(cfg.kinds) == {'mla'}:
        return [('moe' if m else 'dense', n, m, kind)
                for _, n, m, kind in runs]
    return [(f'{i}_{kind}_{"moe" if m else "dense"}', n, m, kind)
            for i, n, m, kind in runs]


def init_params(key: jax.Array, cfg: MlaMoeConfig) -> Params:
    """Stacked-by-layer parameters (scan layout): matrices
    N(0, 1 / fan_in), norms 1."""
    def draw(k, shape, fan_in):
        if not fan_in:
            return jnp.ones(shape, cfg.dtype)
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    d = cfg.d_model
    out: Params = {
        'embed': draw(jax.random.fold_in(key, 0), (cfg.vocab_size, d), 1.0),
        'final_norm': jnp.ones((d,), cfg.dtype),
        'lm_head': draw(jax.random.fold_in(key, 1), (d, cfg.vocab_size), d)}
    for si, (name, n_l, is_moe, kind) in enumerate(_stacks(cfg)):
        shapes = _layer_shapes(cfg, is_moe, kind)
        ks = jax.random.split(jax.random.fold_in(key, 2 + si), len(shapes))
        out[name] = {leaf: draw(k, (n_l,) + shape, fan_in)
                     for k, (leaf, (shape, _, fan_in))
                     in zip(ks, shapes.items())}
    return out


def param_logical_axes(cfg: MlaMoeConfig) -> Params:
    """Logical sharding axes matching ``init_params``' tree."""
    out: Params = {'embed': ('vocab', 'embed'), 'final_norm': (None,),
                   'lm_head': ('embed', 'vocab')}
    for name, _, is_moe, kind in _stacks(cfg):
        out[name] = {leaf: ('layers',) + axes for leaf, (_, axes, _)
                     in _layer_shapes(cfg, is_moe, kind).items()}
    return out


# -- rotary -----------------------------------------------------------------


def _inv_freq(cfg: MlaMoeConfig) -> np.ndarray:
    """Rotary frequencies [rope / 2]; YaRN blends ``theta^(-2i/dim)``
    with itself over ``factor`` by how often a dim turns over the
    original context (``beta_fast`` turns and more: kept; ``beta_slow``
    and fewer: divided)."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor, orig, fast, slow = cfg.rope_yarn[:4]
    if factor == 1.0:
        return freq.astype(np.float32)

    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (freq / factor * ramp + freq * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg: MlaMoeConfig) -> float:
    """``(nope + rope)^-1/2``, times YaRN's ``mscale^2`` (cos and sin
    stay unscaled when ``mscale == mscale_all_dim``, which is asserted)."""
    factor, _, _, _, mscale, all_dim = cfg.rope_yarn
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if factor == 1.0 or not cfg.rope:
        return scale
    if mscale != all_dim:
        raise NotImplementedError('YaRN with mscale != mscale_all_dim '
                                  'scales cos/sin: not implemented')
    m = 0.1 * all_dim * math.log(factor) + 1.0
    return scale * m * m


def _rope(x: jax.Array, positions: jax.Array, cfg: MlaMoeConfig):
    """x [B, S, ..., Dr], positions [B, S]; rotate-half (``cfg.rope``
    False: x as it is)."""
    if not cfg.rope:
        return x
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * _inv_freq(cfg)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


# -- hyper-connections ------------------------------------------------------


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m [..., n, n] positive float32 -> doubly stochastic: ``iters``
    rounds of rows then columns, unrolled (elementwise work on n x n
    numbers a token: one fusion, not ``2 * iters`` launches)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _hc_maps(cfg: MlaMoeConfig, xs: jax.Array, layer: Params, sub: str):
    """xs [B, S, n, d] -> (H_pre [B,S,n], H_post [B,S,n], H_res
    [B,S,n,n]), float32."""
    with jax.named_scope('hc.maps'):
        b, s, n, d = xs.shape
        flat = xs.reshape(b, s, n * d).astype(jnp.float32)
        var = jnp.mean(flat * flat, axis=-1, keepdims=True)
        # float32 throughout (n*n + 2n = 24 columns: nothing beside the
        # layer's matmuls even at six passes)
        m = jnp.einsum('bsk,kj->bsj', flat * jax.lax.rsqrt(var + cfg.hc_eps),
                       layer[f'hc_{sub}_phi'].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        alpha = layer[f'hc_{sub}_alpha'].astype(jnp.float32)
        bias = layer[f'hc_{sub}_bias'].astype(jnp.float32)
        pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n]
                                    + bias[n:2 * n])
        res = jnp.clip(alpha[2] * m[..., 2 * n:] + bias[2 * n:],
                       *cfg.hc_clamp)
        res = sinkhorn(jnp.exp(res).reshape(b, s, n, n),
                       cfg.hc_sinkhorn_iters, cfg.hc_eps)
        return pre, post, res


def _sublayer(cfg: MlaMoeConfig, xs: jax.Array, layer: Params, sub: str,
              fn):
    """One hyper-connected sub-layer around ``fn`` ([B, S, d] ->
    ([B, S, d], extra)); -> (xs', extra). The mixing is float32 at
    HIGHEST (a float32 product at the TPU's default precision rounds its
    operands to bfloat16); the stream is stored in the model's dtype.
    (A float32 stream and a float32 router input were tried against the
    reference on the chip: the same tokens flip experts, PERF.md PR 28.)"""
    norm = layer[f'{sub}_norm']
    if cfg.hc_mult == 1:
        x = xs[:, :, 0]
        y, extra = fn(rms_norm(x, norm, cfg.norm_eps))
        return (x + y)[:, :, None], extra
    pre, post, res = _hc_maps(cfg, xs, layer, sub)
    exact = jax.lax.Precision.HIGHEST
    x32 = xs.astype(jnp.float32)
    h = jnp.einsum('bsn,bsnd->bsd', pre, x32, precision=exact)
    y, extra = fn(rms_norm(h.astype(xs.dtype), norm, cfg.norm_eps))
    out = (jnp.einsum('bsij,bsjd->bsid', res, x32, precision=exact)
           + post[..., None] * y.astype(jnp.float32)[:, :, None, :])
    return out.astype(xs.dtype), extra


# -- attention --------------------------------------------------------------


def _q_and_latent(cfg: MlaMoeConfig, h: jax.Array, layer: Params,
                  positions: jax.Array):
    """h [B, S, d] -> (q [B, S, H, nope + rope] with rotary applied,
    latent rows [B, S, W]: ``RMSNorm(c_kv) | rope(k_rope) | 0``)."""
    if 'wq' in layer:       # no query down-projection
        q = jnp.einsum('bsd,dhk->bshk', h, layer['wq'])
    else:
        c_q = rms_norm(jnp.einsum('bsd,dr->bsr', h, layer['wq_a']),
                       layer['q_norm'], cfg.norm_eps)
        q = jnp.einsum('bsr,rhk->bshk', c_q, layer['wq_b'])
    nope = cfg.qk_nope_dim
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], positions,
                                              cfg)], -1)
    kv = jnp.einsum('bsd,dr->bsr', h, layer['wkv_a'])
    rank = cfg.kv_lora_rank
    c_kv = rms_norm(kv[..., :rank], layer['kv_norm'], cfg.norm_eps)
    k_rope = _rope(kv[..., rank:], positions, cfg)
    pad = jnp.zeros(kv.shape[:-1] + (cfg.latent_width - cfg.latent_dim,),
                    kv.dtype)
    return q, jnp.concatenate([c_kv, k_rope, pad], -1)


def _expand(cfg: MlaMoeConfig, latent: jax.Array, layer: Params):
    """Latent rows [B, M, W] -> per-head K [B, M, H, nope + rope] and V
    [B, M, H, v]."""
    with jax.named_scope('mla.expand'):
        rank, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
        up = jnp.einsum('bmr,rhk->bmhk', latent[..., :rank], layer['wkv_b'])
        k_rope = latent[..., None, rank:cfg.latent_dim]
        k = jnp.concatenate(
            [up[..., :nope],
             jnp.broadcast_to(k_rope, up.shape[:3] + k_rope.shape[-1:])],
            -1)
        return k, up[..., nope:]


def _attend_fresh(cfg: MlaMoeConfig, q: jax.Array, latent: jax.Array,
                  layer: Params) -> jax.Array:
    """Causal attention of a prefill that starts at position 0 (padded
    on the right): the flash kernel on a TPU, whose one head size takes
    V zero-padded to K's width and whose ``D^-1/2`` takes the rest of
    the softmax scale folded into q. -> [B, S, H, v]."""
    k, v = _expand(cfg, latent, layer)
    dk = q.shape[-1]
    fold = softmax_scale(cfg) * dk ** 0.5
    qt = (q * fold).astype(q.dtype).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0),) * 3 + ((0, dk - v.shape[-1]),))
    att = attention_ops.flash_attention(
        qt, k.transpose(0, 2, 1, 3), vt.transpose(0, 2, 1, 3), causal=True)
    return att.transpose(0, 2, 1, 3)[..., :cfg.v_head_dim]


def _attend_view(cfg: MlaMoeConfig, q: jax.Array, view: jax.Array,
                 layer: Params, positions: jax.Array,
                 valid: jax.Array) -> jax.Array:
    """Expanded attention of q [B, S, H, Dk] (absolute ``positions``
    [B, S]) over a row view of the cache [B, M, W] that already holds
    this call's rows: causal, and only the first ``valid[b]`` positions.
    Queries go ``_QUERY_BLOCK`` at a time. -> [B, S, H, v]."""
    k, v = _expand(cfg, view, layer)
    scale = softmax_scale(cfg)
    ki = jnp.arange(view.shape[1], dtype=jnp.int32)[None, None, None, :]

    def block(args):
        qb, pb = args                           # [B, s, H, Dk], [B, s]
        logits = jnp.einsum('bshk,bmhk->bhsm', qb, k,
                            preferred_element_type=jnp.float32) * scale
        mask = ((ki <= pb[:, None, :, None])
                & (ki < valid[:, None, None, None]))
        probs = jax.nn.softmax(jnp.where(mask, logits, _NEG_INF), axis=-1)
        return jnp.einsum('bhsm,bmhv->bshv', probs.astype(q.dtype), v)

    b, s = q.shape[:2]
    if s <= _QUERY_BLOCK or s % _QUERY_BLOCK:
        return block((q, positions))
    nblk = s // _QUERY_BLOCK
    out = jax.lax.map(block, (
        q.reshape(b, nblk, _QUERY_BLOCK, *q.shape[2:]).swapaxes(0, 1),
        positions.reshape(b, nblk, _QUERY_BLOCK).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, s, *out.shape[3:])


def _absorb(cfg: MlaMoeConfig, q: jax.Array, layer: Params) -> jax.Array:
    """q [B, H, nope + rope] -> absorbed [B, H, rank + rope]:
    ``q_nope W_UK^T | q_rope``, so that its dot with a cached row is the
    expanded path's ``q . [k_nope | k_rope]``."""
    with jax.named_scope('mla.absorb'):
        nope = cfg.qk_nope_dim
        q_lat = jnp.einsum('bhk,rhk->bhr', q[..., :nope],
                           layer['wkv_b'][..., :nope])
        return jnp.concatenate([q_lat, q[..., nope:]], -1)


def _unabsorb(cfg: MlaMoeConfig, o_lat: jax.Array, layer: Params):
    """Attention-weighted latents [B, H, rank] -> values [B, H, v]."""
    with jax.named_scope('mla.absorb'):
        return jnp.einsum('bhr,rhv->bhv', o_lat,
                          layer['wkv_b'][..., cfg.qk_nope_dim:])


def _absorbed_view(cfg: MlaMoeConfig, q_abs: jax.Array, view: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """The absorbed step over a dense view [B, M, W] in plain jnp: what
    backends without the kernel (and the dense cache) take."""
    rank = cfg.kv_lora_rank
    logits = jnp.einsum('bhr,bmr->bhm', q_abs, view[..., :cfg.latent_dim],
                        preferred_element_type=jnp.float32)
    logits = logits * softmax_scale(cfg)
    ki = jnp.arange(view.shape[1], dtype=jnp.int32)[None, None, :]
    logits = jnp.where(ki < valid[:, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q_abs.dtype)
    return jnp.einsum('bhm,bmr->bhr', probs, view[..., :rank])


def decode_path(tables_shape, pool_shape, dtype) -> str:
    """``'mla_kernel'`` (``decode_attention.mla_decode``) or ``'gather'``
    for the S = 1 step over a latent pool: ``paged.decode_path``'s rule
    (a TPU, or the interpreter where a test asks for it by name; a
    geometry the kernel takes)."""
    if not (attention_ops._use_pallas()
            or decode_attention.PAGED_INTERPRET):
        return 'gather'
    (b, mb), p = tables_shape, pool_shape[-2]
    if decode_attention.mla_fits(b, mb, p, dtype):
        return 'mla_kernel'
    attention_ops.log_fallback_once(
        'mla_decode', (b, mb, p), f'B={b}, MB={mb}, P={p} outside mla_fits()')
    return 'gather'


def step_paths(cfg: MlaMoeConfig, pool, prefill_tokens: int
               ) -> Dict[str, Any]:
    """``ModelOps.step_paths`` of this family: how the routed experts'
    grouped matmuls run (``moe.grouped_path``) in a decode step of the
    pool's slots and in a prefill of ``prefill_tokens`` (one piece, or
    one whole prompt), under ``moe_grouped``; {} without routed
    experts."""
    if not cfg.num_experts:
        return {}
    lo, hi = cfg.held

    def path(tokens):
        return moe.grouped_path(tokens * cfg.expert_top_k, hi - lo,
                                cfg.d_model, cfg.d_ff_expert, cfg.dtype)

    return {'moe_grouped': {'decode': path(pool.tables.shape[0]),
                            'prefill': path(prefill_tokens)}}


def _ffn(cfg: MlaMoeConfig, h: jax.Array, layer: Params, token_mask):
    """Dense SwiGLU or the drop-free expert layer on h [B, S, d];
    -> (y, per-expert counts or None). ``layer['stack_layer']``: the
    ``we_*`` leaves are the whole stack (``_layers``)."""
    if 'we_gate' not in layer:
        gate = jnp.einsum('bsd,df->bsf', h, layer['w_gate'])
        up = jnp.einsum('bsd,df->bsf', h, layer['w_up'])
        return jnp.einsum('bsf,fd->bsd', jax.nn.silu(gate) * up,
                          layer['w_down']), None
    b, s, d = h.shape
    y, load = moe.dropfree_mlp(
        h.reshape(b * s, d), layer, cfg.expert_top_k, cfg.routed_scale,
        cfg.norm_topk_prob, cfg.held,
        None if token_mask is None else token_mask.reshape(b * s),
        layer.get('stack_layer'))
    return y.reshape(b, s, d), load


def _wo(att: jax.Array, layer: Params) -> jax.Array:
    return jnp.einsum('bshv,hvd->bsd', att, layer['wo'])


def _layers(cfg: MlaMoeConfig, params: Params, xs: jax.Array, cache_arr,
            mixers, token_mask):
    """Every stack over the residual ``xs`` [B, S, n, d]. ``cache_arr``
    (any pytree: the latent planes [L_mla, ...], and the KDA state
    beside them where there are such layers) rides as a carry;
    ``mixers[kind](h, layer, cache_arr, l) -> (y [B, S, d], cache_arr)``
    is the caller's cache strategy for a layer of that kind, ``l`` the
    layer's index among the layers of its KIND (which is where its
    part of the cache lies). -> (xs, cache_arr, expert counts [E]).
    The routed experts' weights are NOT scanned: every layer is handed
    the whole stack and its index in it (``moe.dropfree_mlp``)."""
    load0 = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
    seen = {kind: 0 for kind in mixers}
    for name, n_l, _, kind in _stacks(cfg):
        l0, mix = seen[kind], mixers[kind]
        whole = {k: v for k, v in params[name].items()
                 if k in ('we_gate', 'we_up', 'we_down')}
        scanned = {k: v for k, v in params[name].items() if k not in whole}

        def body(carry, step):
            xs, arr, load = carry
            layer, i = step
            l = l0 + i
            if whole:
                layer = dict(layer, stack_layer=i, **whole)
            xs, arr = _sublayer(cfg, xs, layer, 'attn',
                                lambda h: mix(h, layer, arr, l))
            xs, cnt = _sublayer(cfg, xs, layer, 'mlp',
                                lambda h: _ffn(cfg, h, layer, token_mask))
            return (xs, arr, load if cnt is None else load + cnt), None

        (xs, cache_arr, load0), _ = jax.lax.scan(
            body, (xs, cache_arr, load0),
            (scanned, jnp.arange(n_l, dtype=jnp.int32)))
        seen[kind] += n_l
    return xs, cache_arr, load0


def _embed(cfg: MlaMoeConfig, params: Params, tokens: jax.Array):
    x = params['embed'].astype(cfg.dtype)[tokens]
    return jnp.broadcast_to(x[:, :, None, :],
                            x.shape[:2] + (cfg.hc_mult, x.shape[-1]))


def _head(cfg: MlaMoeConfig, params: Params, xs: jax.Array,
          index: Optional[jax.Array], all_logits: bool = False):
    """Fold the streams (sum), final norm, output head at each row's
    position ``index`` [B] (None = the last; ``all_logits``: every)."""
    x = jnp.sum(xs.astype(jnp.float32), axis=2).astype(xs.dtype)
    x = rms_norm(x, params['final_norm'], cfg.norm_eps)
    if all_logits:
        return jnp.einsum('bsd,dv->bsv', x, params['lm_head'],
                          preferred_element_type=jnp.float32)
    if index is None:
        last = x[:, -1]
    else:
        last = jnp.take_along_axis(
            x, index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return jnp.einsum('bd,dv->bv', last, params['lm_head'],
                      preferred_element_type=jnp.float32)


# -- dense cache: prefill (and the window path's decode) ---------------------


@dataclasses.dataclass
class StateKVCache(KVCache):
    """The dense latent cache with, beside it, what the KDA layers keep
    a row: ``state`` [L_kda, B, H, dk, dv] float32 and ``conv``
    [L_kda, B, cw - 1, 3 H dk] (``kda.py``)."""
    state: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None


@dataclasses.dataclass
class LatentStatePool(PagedKVCache):
    """The latent pool with the KDA layers' state beside it, a row a
    SLOT: what the block table pages is the MLA layers' alone."""
    state: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None


for _cls in (StateKVCache, LatentStatePool):
    jax.tree_util.register_dataclass(
        _cls, data_fields=[f.name for f in dataclasses.fields(_cls)],
        meta_fields=[])


def _state_fields(cfg: MlaMoeConfig, rows: int) -> Dict[str, jax.Array]:
    n = cfg.n_kind('kda')
    return {'state': jnp.zeros((n,) + kda.state_shape(cfg, rows),
                               jnp.float32),
            'conv': jnp.zeros((n,) + kda.tail_shape(cfg, rows), cfg.dtype)}


def _carry(cache):
    """What of a cache rides the layer scans: (latent planes, KDA
    state, convolution tails), the last two None where the model has
    no such layer (empty pytrees: the scans carry what they did)."""
    return cache.k, getattr(cache, 'state', None), getattr(cache, 'conv',
                                                           None)


def _kda_mixer(cfg: MlaMoeConfig, row_lens: Optional[jax.Array],
               live: Optional[jax.Array]):
    """``_layers``' mixer for a KDA layer over the carry of ``_carry``:
    the one-token recurrence for S == 1, else the chunked form
    continuing the row's state; the row's first ``row_lens`` positions
    are real, rows not ``live`` leave their state alone."""
    def mix(h, layer, carry, l):
        arr, state, conv = carry
        if h.shape[1] == 1:
            alive = jnp.ones(h.shape[:1], bool) if live is None else live
            y, state, conv = kda.step_layer(cfg, h[:, 0], layer, state, conv,
                                            l, alive)
            return y[:, None], (arr, state, conv)
        if row_lens is None:
            raise ValueError('a KDA layer over more than one position '
                             'needs each row\'s length (row_lens): its '
                             'state is taken there')
        y, s_new, c_new = kda.forward(cfg, h, layer, state[l], conv[l],
                                      row_lens, live)
        return y, (arr, state.at[l].set(s_new), conv.at[l].set(c_new))
    return mix


def init_cache(cfg: MlaMoeConfig, batch: int, max_len: int, dtype=None,
               kv_sharding=None, lengths_sharding=None,
               quantize: bool = False, kv_scale_sharding=None) -> KVCache:
    """The dense latent cache [L_mla, B, 1, max_len, W]: ``KVCache``
    with ONE plane (``v`` None); with KDA layers, their state too."""
    if quantize:
        raise ValueError('the latent (MLA) cache has no int8 mode')
    shape = (cfg.n_kind('mla'), batch, 1, max_len, cfg.latent_width)
    k = jnp.zeros(shape, dtype or cfg.dtype, device=kv_sharding)
    lengths = jnp.zeros((batch,), jnp.int32, device=lengths_sharding)
    if cfg.n_kind('kda'):
        return StateKVCache(k=k, v=None, lengths=lengths,
                            **_state_fields(cfg, batch))
    return KVCache(k=k, v=None, lengths=lengths)


def forward_cached(params: Params, tokens: jax.Array, cache: KVCache,
                   cfg: MlaMoeConfig, row_lens: Optional[jax.Array] = None,
                   active_rows: Optional[jax.Array] = None,
                   all_logits: bool = False
                   ) -> Tuple[jax.Array, KVCache]:
    """``generate.forward_cached`` for this model: run ``tokens`` [B, S]
    appending their latent rows to the dense ``cache``; logits at each
    row's last real position. A cache exactly S wide can hold no prefix,
    so S == max_len is a FRESH prefill (flash kernel); S == 1 is the
    absorbed step; anything else attends expanded over the row's view."""
    b, s = tokens.shape
    m = cache.k.shape[3]
    if row_lens is None:
        row_lens = jnp.full((b,), s, jnp.int32)
    start = cache.lengths
    positions = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    valid = start + row_lens
    token_mask = positions < valid[:, None]
    if active_rows is not None:
        token_mask = token_mask & active_rows[:, None]

    def attend(h, layer, carry, l):
        arr = carry[0]
        q, latent = _q_and_latent(cfg, h, layer, positions)
        rows = jax.vmap(lambda c, n, st: jax.lax.dynamic_update_slice(
            c, n, (st, 0)))(arr[l, :, 0], latent.astype(arr.dtype), start)
        arr = arr.at[l, :, 0].set(rows)
        if s == m:
            att = _attend_fresh(cfg, q, latent, layer)
        elif s == 1:
            att = _unabsorb(cfg, _absorbed_view(
                cfg, _absorb(cfg, q[:, 0], layer), rows, valid),
                layer)[:, None]
        else:
            att = _attend_view(cfg, q, rows, layer, positions, valid)
        return _wo(att, layer), (arr,) + carry[1:]

    mixers = {'mla': attend, 'kda': _kda_mixer(cfg, row_lens, active_rows)}
    xs, (arr, state, conv), _ = _layers(
        cfg, params, _embed(cfg, params, tokens), _carry(cache), mixers,
        token_mask)
    logits = _head(cfg, params, xs, row_lens - 1, all_logits)
    if state is None:
        return logits, KVCache(k=arr, v=None, lengths=valid)
    return logits, StateKVCache(k=arr, v=None, lengths=valid, state=state,
                                conv=conv)


jit_prefill = profiled_jit('mla_moe.prefill', forward_cached,
                           static_argnums=(3,))


# -- paged pool ---------------------------------------------------------------


def init_pool(cfg: MlaMoeConfig, slots: int, max_len: int, n_blocks: int,
              block: int, quantize: bool = False, kv_sharding=None,
              scale_sharding=None, lengths_sharding=None) -> PagedKVCache:
    """``paged.init_pool`` with ONE latent plane [L_mla, NB, 1, P, W]
    (``v`` None). Block 0 is the junk sink, as ever. With KDA layers,
    their state a slot beside it (``LatentStatePool``)."""
    if quantize:
        raise ValueError('the latent (MLA) pool has no int8 mode')
    if block < 1 or block & (block - 1) or max_len % block:
        raise ValueError(f'block size {block} must be a power of two that '
                         f'divides max_len {max_len}')
    shape = (cfg.n_kind('mla'), n_blocks, 1, block, cfg.latent_width)
    fields = dict(
        k=jnp.zeros(shape, cfg.dtype, device=kv_sharding), v=None,
        tables=jnp.zeros((slots, max_len // block), jnp.int32),
        lengths=jnp.zeros((slots,), jnp.int32, device=lengths_sharding))
    if cfg.n_kind('kda'):
        return LatentStatePool(**fields, **_state_fields(cfg, slots))
    return PagedKVCache(**fields)


def _insert_impl(pool: LatentStatePool, cache_n: StateKVCache,
                 tables_new: jax.Array, slots: jax.Array) -> LatentStatePool:
    """``paged._insert_impl`` for a pool with state: the rows' latent
    blocks through their tables, and each row's FINAL state and tails
    (taken at the row's own length by the prefill) into its slot,
    whatever the slot held."""
    kv = paged._insert_impl(pool, cache_n, tables_new, slots)
    return LatentStatePool(
        k=kv.k, v=None, tables=kv.tables, lengths=kv.lengths,
        state=pool.state.at[:, slots].set(cache_n.state),
        conv=pool.conv.at[:, slots].set(cache_n.conv))


jit_insert = profiled_jit('mla_moe.insert', _insert_impl,
                          donate_argnums=(0,))


def _pool_view(pool: jax.Array, l, tables: jax.Array) -> jax.Array:
    """Every row's whole table out of layer ``l`` (``paged.pool_view``:
    the named blocks only, no plane is sliced out): [B, MB * P, W]."""
    g = pool_view(pool, l, tables)               # [B, MB, 1, P, W]
    return g.reshape(g.shape[0], -1, g.shape[-1])


def forward_paged(params: Params, tokens: jax.Array, cache: PagedKVCache,
                  cfg: MlaMoeConfig, active_rows: Optional[jax.Array] = None,
                  logit_index: Optional[jax.Array] = None):
    """``paged.forward_paged`` for this model: S == 1 is the decode
    step (absorbed, through the table, by length), S > 1 the padded
    shared-prefix prefill (expanded over the row's view).
    -> (logits, cache advanced S, expert counts [E])."""
    b, s = tokens.shape
    lengths, tables = cache.lengths, cache.tables
    positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    token_mask = None
    if logit_index is not None:
        token_mask = positions <= (lengths + logit_index)[:, None]
    if active_rows is not None:
        token_mask = (active_rows[:, None] if token_mask is None
                      else token_mask & active_rows[:, None])
    path = decode_path(tables.shape, cache.k.shape, cache.k.dtype)
    live = jnp.ones((b,), bool) if active_rows is None else active_rows

    def attend(h, layer, carry, l):
        pool, rest = carry[0], carry[1:]
        q, latent = _q_and_latent(cfg, h, layer, positions)
        pool = pool_write(pool, l, tables, lengths, latent[:, None],
                          active_rows)
        if s > 1:
            att = _attend_view(cfg, q, _pool_view(pool, l, tables), layer,
                               positions, lengths + s)
            return _wo(att, layer), (pool,) + rest
        q_abs = _absorb(cfg, q[:, 0], layer)
        # inactive rows read nothing: their stale tables may name
        # blocks that now belong to another request
        valid = jnp.where(live, lengths + 1, 0)
        if path == 'mla_kernel':
            o_lat = decode_attention.mla_decode(
                q_abs, pool, l, tables, valid, cfg.kv_lora_rank,
                softmax_scale(cfg),
                interpret=not attention_ops._use_pallas())
        else:
            o_lat = _absorbed_view(cfg, q_abs, _pool_view(pool, l, tables),
                                   valid)
        return (_wo(_unabsorb(cfg, o_lat, layer)[:, None], layer),
                (pool,) + rest)

    real = None if logit_index is None else logit_index + 1
    mixers = {'mla': attend, 'kda': _kda_mixer(cfg, real, active_rows)}
    xs, (pool, state, conv), load = _layers(
        cfg, params, _embed(cfg, params, tokens), _carry(cache), mixers,
        token_mask)
    logits = _head(cfg, params, xs, logit_index)
    if state is None:
        return logits, PagedKVCache(k=pool, v=None, tables=tables,
                                    lengths=lengths + s), load
    return logits, LatentStatePool(k=pool, v=None, tables=tables,
                                   lengths=lengths + s, state=state,
                                   conv=conv), load


def _prefill_shared_impl(cfg: MlaMoeConfig, params, cache: PagedKVCache,
                         tokens: jax.Array, table_row: jax.Array,
                         slot: jax.Array, start: jax.Array,
                         slen: jax.Array, shard_ctx=None):
    """``paged._prefill_shared_impl`` for this model: the unshared tail
    [1, W] prefilled directly over the pool, reading the shared prefix
    through ``table_row`` (expanded attention over the row's view)."""
    del shard_ctx
    row = PagedKVCache(k=cache.k, v=None, tables=table_row, lengths=start)
    logits, row, _ = forward_paged(params, tokens, row, cfg,
                                   logit_index=slen - 1)
    return logits, PagedKVCache(
        k=row.k, v=None, tables=cache.tables.at[slot].set(table_row[0]),
        lengths=cache.lengths.at[slot].set(start[0] + slen[0]))


jit_prefill_shared = profiled_jit('mla_moe.prefill_shared',
                                  _prefill_shared_impl,
                                  static_argnums=(0, 8), donate_argnums=(2,))


def _paged_chunk_impl(cfg: MlaMoeConfig, k_steps: int, params, cache,
                      last: jax.Array, temps: jax.Array, top_ks, top_ps,
                      active: jax.Array, key: jax.Array, shard_ctx=None):
    """``engine._paged_chunk_impl`` for this model: K decode steps over
    the latent pool. Besides (cache, last, toks[K, B]) it returns the
    experts' token counts [E] summed over the chunk's steps and expert
    layers (live rows only): they ride back with the tokens, no sync of
    their own."""
    del shard_ctx

    def step(carry, key_t):
        cache, last, load = carry
        logits, cache, cnt = forward_paged(params, last[:, None], cache,
                                           cfg, active)
        nxt = sampling.sample(logits, temps, key_t, top_ks, top_ps)
        return (cache, nxt, load + cnt), nxt

    load0 = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
    (cache, last, load), toks = jax.lax.scan(
        step, (cache, last, load0), jax.random.split(key, k_steps))
    return cache, last, toks, load


jit_paged_chunk = profiled_jit('mla_moe.paged_chunk', _paged_chunk_impl,
                               static_argnums=(0, 1, 10),
                               donate_argnums=(3, 4))


def _paged_chunk_n_impl(cfg: MlaMoeConfig, k_steps: int, params, cache,
                        last: jax.Array, temps: jax.Array, top_ks, top_ps,
                        active: jax.Array, key: jax.Array,
                        n_steps: jax.Array):
    """``_paged_chunk_impl`` that stops after ``n_steps`` <= K steps (a
    device scalar: ONE program for every length). Step ``i`` draws with
    the key the full chunk gives it, so the first ``n_steps`` rows of
    ``toks[K, B]`` are the full chunk's; the rest stay zero. Pool and
    state ride the loop's carry in place, as they ride the scan's."""
    keys = jax.random.split(key, k_steps)

    def step(i, carry):
        cache, last, load, toks = carry
        logits, cache, cnt = forward_paged(params, last[:, None], cache,
                                           cfg, active)
        nxt = sampling.sample(logits, temps, keys[i], top_ks, top_ps)
        return cache, nxt, load + cnt, toks.at[i].set(nxt)

    load0 = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
    toks0 = jnp.zeros((k_steps,) + last.shape, last.dtype)
    cache, last, load, toks = jax.lax.fori_loop(
        0, n_steps, step, (cache, last, load0, toks0))
    return cache, last, toks, load


jit_paged_chunk_n = profiled_jit('mla_moe.paged_chunk_n',
                                 _paged_chunk_n_impl,
                                 static_argnums=(0, 1),
                                 donate_argnums=(3, 4))
