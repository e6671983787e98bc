"""What decides ``correct``: the numbers compared, each beside its
limit. The limits live in ``benchmarks/limits/<cell>.json`` and were
set from readings on the chip (PERF.md gives them); nothing here
chooses one.

Serving: over a sample (drawn from the seed, the longest request in
it) of the requests the window finished, the plain reference is run
once over each prompt with its served tokens, and each served token's
gap is read: how far its reference logit lies below the reference's
best at that position. Greedy decoding in the stated precision picks
the reference's best or a near-tie; a wrong cache, a lost prefix
block, a missing all-reduce or a lower precision picks something else.
The number compared is the MEAN gap over the sample's tokens
(``gap_mean``): on the chip the widest gap (``gap_max``, still printed)
is an extreme of some hundreds of near-ties and swings by seed from
0.02 to 0.06 while the int8 control's smallest reads 0.15, under three
times that, so it separates nothing; the mean reads 0.0002-0.0008
against the control's 0.007-0.011 (PERF.md, limits). ``missing`` counts
counted requests that never finished or came back with another number
of tokens than asked; its limit is 0.

Training: see ``train_numbers``.
"""
from __future__ import annotations

import math
import statistics
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.traffic_gen import pad_width


def serving_gaps(params, cfg: Dict[str, Any], sample: Sequence,
                 quant: Optional[str] = None, pad_lo: int = 256
                 ) -> Dict[str, float]:
    """Gaps of the served tokens under the reference. With ``quant``
    the CONTROL is read instead: at each position, the gap of the
    token the lower precision puts first."""
    import jax
    from benchmarks.reference import llama as ref
    gaps: List[float] = []
    for rec in sample:
        served = list(rec.tokens)[:rec.max_new]
        if not served:
            continue
        seq = list(rec.prompt) + served[:-1]
        rows = list(range(len(rec.prompt) - 1,
                          len(rec.prompt) - 1 + len(served)))
        t_pad = pad_width(len(seq), pad_lo)
        r_pad = pad_width(len(rows), 64)
        toks = np.zeros((t_pad,), np.int32)
        toks[:len(seq)] = seq
        rws = np.zeros((r_pad,), np.int32)
        rws[:len(rows)] = rows
        logits = ref.logits_at(params, toks, rws, cfg, None)
        best = np.asarray(jax.device_get(logits.max(axis=-1)))[:len(rows)]
        if quant is None:
            pick = np.asarray(served, np.int64)
        else:
            low = ref.logits_at(params, toks, rws, cfg, quant)
            pick = np.asarray(jax.device_get(low.argmax(axis=-1)))[
                :len(rows)].astype(np.int64)
        idx = np.zeros((r_pad,), np.int32)
        idx[:len(rows)] = pick
        got = np.asarray(jax.device_get(
            logits[np.arange(r_pad), idx]))[:len(rows)]
        gaps.extend((best - got).tolist())
    if not gaps:
        return {'gap_max': float('inf'), 'gap_mean': float('inf'),
                'tokens': 0}
    return {'gap_max': float(max(gaps)),
            'gap_mean': float(sum(gaps) / len(gaps)),
            'tokens': len(gaps)}


def missing_answers(records: Sequence) -> int:
    """Counted requests that never finished, failed, or came back with
    another number of tokens than asked."""
    return sum(1 for r in records if r.counted
               and (not r.finished or r.n_tokens != r.max_new
                    or len(r.tokens) != r.max_new))


# -- training ----------------------------------------------------------------


def grad_norms_from_adafactor(opt_state, params, clip_norm: float,
                              global_norm: float) -> Dict[str, float]:
    """Per-leaf norm of the FIRST gradient as the optimizer chain got
    it, worked out from the state after one step. At step 1 Adafactor's
    decay is 0, so its second-moment statistics ARE the (clipped)
    gradient's squares: a factored leaf keeps their row means, so
    sum(g^2) = sum(v_row) * (extent of the averaged axis); an
    unfactored leaf keeps g^2 itself. The clip before it scaled every
    leaf by ``clip_norm / global_norm`` when the global norm passed
    ``clip_norm``; that is undone here."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference.train import factored_dims
    fact = None
    for part in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, 'v_row')):
        if hasattr(part, 'v_row'):
            fact = part
            break
    if fact is None:
        raise ValueError('no factored second-moment state in opt_state')
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    rows = jax.tree.leaves(fact.v_row)
    full = jax.tree.leaves(fact.v)
    # skylint: allow-jit(benchmark-side program: the reference and the
    # harness are outside the serving compile ledger by design)
    sums = jax.jit(lambda a, b: ([jnp.sum(x.astype(jnp.float32))
                                  for x in a],
                                 [jnp.sum(x.astype(jnp.float32))
                                  for x in b]))(rows, full)
    unclip = max(global_norm / clip_norm, 1.0)
    out: Dict[str, float] = {}
    for i, (path, p) in enumerate(flat_p):
        dims = factored_dims(p.shape)
        if dims is None:
            sq = float(sums[1][i])
        else:
            sq = float(sums[0][i]) * p.shape[dims[1]]
        out[jax.tree_util.keystr(path)] = math.sqrt(max(sq, 0.0)) * unclip
    return out


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[Sequence[str]] = None
                   ) -> Tuple[float, str]:
    """max over leaves of |prog - ref| / max(ref leaf, median ref
    leaf): the gap between the two norms, not the norm of a
    difference, against the leaf's own size or the median leaf's,
    whichever is larger (some gradients are all but zero)."""
    keys = [k for k in ref if keep is None or k in keep]
    if not keys:
        return 0.0, ''
    med = statistics.median(ref[k] for k in keys)
    worst, name = 0.0, ''
    for k in keys:
        gap = abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, name = gap, k
    return worst, name


def moving_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's. The others move under an
    adaptive optimizer by round-off alone and are left out of the
    change's comparison, by this rule and not by name."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= 1e-3 * med]


def train_numbers(prog: Dict[str, Any], ref: Dict[str, Any]
                  ) -> Dict[str, float]:
    """The training cell's numbers from the two sides' readings
    (``losses`` per step, ``grad`` per-leaf norms of the first
    gradient, ``grad_global``, ``change`` per-leaf norms of the
    parameters' change over the steps followed)."""
    n = min(len(prog['losses']), len(ref['losses']))
    loss_gap = max(abs(prog['losses'][i] - ref['losses'][i])
                   / abs(ref['losses'][i]) for i in range(n))
    grad_gap, grad_leaf = worst_leaf_gap(prog['grad'], ref['grad'])
    keep = moving_leaves(ref['grad'])
    change_gap, change_leaf = worst_leaf_gap(prog['change'], ref['change'],
                                             keep)
    gnorm_gap = (abs(prog['grad_global'] - ref['grad_global'])
                 / ref['grad_global'])
    return {'loss_gap': loss_gap, 'grad_global_gap': gnorm_gap,
            'grad_leaf_gap': grad_gap, 'change_leaf_gap': change_gap,
            '_grad_leaf': grad_leaf, '_change_leaf': change_leaf}


# -- the verdict -------------------------------------------------------------


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, List[float]]]:
    """``correct`` and the table {name: [number, limit]} of every
    number that has a limit. A number that is not finite fails."""
    table: Dict[str, List[float]] = {}
    ok = True
    for name, limit in limits.items():
        if name.startswith('_'):
            continue
        val = numbers.get(name)
        if val is None or not math.isfinite(val) or val > limit:
            ok = False
        table[name] = [val if val is not None else float('nan'), limit]
    return ok, table


def print_table(table: Dict[str, List[float]], correct: bool) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    sys.stderr.flush()
    for name, (val, limit) in table.items():
        print(f'[compared] {name} = {val:.6g} (limit {limit:.6g})',
              file=sys.stderr)
    print(f'[compared] correct = {str(correct).lower()}', file=sys.stderr)
    sys.stderr.flush()
