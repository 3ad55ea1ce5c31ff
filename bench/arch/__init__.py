"""Architectures, one module per architecture, found by the ``reference``
name of a configuration file (its plain reference,
``bench/reference/<name>.py``, has the same name).  A module turns a
configuration file into the served program and counts its work:

- ``model_config(conf)``: the program's ``ModelConfig``; it refuses a
  file that states what the program does not serve;
- ``make_params(conf, seed)``: the program's parameter tree, drawn from
  the seed on the device in one jitted call;
- ``model_flops(conf, prompts, decode_contexts)``: FLOPs of prefilling
  ``prompts`` and decoding one token at each of ``decode_contexts``
  (``bench.flops.model_flops`` finds it; ``step_mfu`` reads it);
- the cost functions of the architecture's own kernels, beside it, for
  the readers of their rooflines.

An architecture whose weights come from ``bench.weights`` also gives its
leaf table there (``GLOBAL``, ``LAYER``, ``shapes(conf)``), which its
reference draws from too.  A new architecture is new files: this module,
its reference, a configuration file that names them, and entries in
``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib
import pkgutil
from typing import Dict, List

__all__ = ["known", "of", "model_config", "make_params"]


def known() -> List[str]:
    """The architectures there are modules for."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def of(conf: Dict):
    """The module of the configuration's architecture."""
    name = conf["reference"]
    if name not in known():
        raise ValueError(f"{conf['name']}: no architecture {name!r} under "
                         f"bench/arch/; known: {', '.join(known())}")
    return importlib.import_module(f"{__name__}.{name}")


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return of(conf).model_config(conf)


def make_params(conf: Dict, seed: int):
    """The program's parameter tree for a configuration file, from the
    seed."""
    return of(conf).make_params(conf, seed)
