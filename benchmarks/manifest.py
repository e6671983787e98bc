"""Finds a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a mix or a metric by name: a later PR adds
``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.json`` (and a ``readers/<reader>.py`` where no
existing reader fits) and the matching entries of ``BENCHMARK.json``,
and edits no file that is there.
"""
from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load(os.path.join(ROOT, 'BENCHMARK.json'))


def cell(name: str, bench: Optional[Dict] = None) -> Dict[str, Any]:
    bench = bench or benchmark()
    for w in bench['workloads']:
        if w['name'] == name:
            return w
    raise KeyError(f'no workload {name!r} in BENCHMARK.json; known: '
                   f'{[w["name"] for w in bench["workloads"]]}')


def config_of(cell_: Dict, bench: Optional[Dict] = None) -> Dict[str, Any]:
    bench = bench or benchmark()
    for c in bench['configs']:
        if c['name'] == cell_['config']:
            out = _load(os.path.join(ROOT, c['file']))
            out['_name'] = c['name']
            return out
    raise KeyError(f'no config {cell_["config"]!r}')


def traffic_of(cell_: Dict) -> Dict[str, Any]:
    mix = _load(os.path.join(HERE, 'traffic', cell_['traffic'] + '.json'))
    mix['_name'] = cell_['traffic']
    return mix


def settings() -> Dict[str, Any]:
    return _load(os.path.join(HERE, 'settings.json'))


def programs() -> List[Dict[str, str]]:
    return _load(os.path.join(HERE, 'programs.json'))


def limits_of(cell_name: str) -> Dict[str, float]:
    """The limits of the numbers ``correct`` compares, by cell."""
    return _load(os.path.join(HERE, 'limits', cell_name + '.json'))


def metric_file(name: str) -> Dict[str, Any]:
    return _load(os.path.join(HERE, 'metrics', name + '.json'))


def reports(metric: Dict, cell_name: str) -> bool:
    w = metric.get('workloads')
    return w is None or cell_name in w


def end_to_end_for(cell_name: str, bench: Optional[Dict] = None
                   ) -> List[Dict]:
    bench = bench or benchmark()
    return [m for m in bench['end_to_end'] if reports(m, cell_name)]


def per_layer_for(cell_name: str, bench: Optional[Dict] = None
                  ) -> List[Dict]:
    bench = bench or benchmark()
    return [m for m in bench['per_layer'] if reports(m, cell_name)]


def reader(name: str):
    """``benchmarks/readers/<name>.py``'s ``read(ctx, **args)``."""
    return importlib.import_module(f'benchmarks.readers.{name}').read
