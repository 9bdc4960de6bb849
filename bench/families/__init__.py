"""Model families, by the ``family`` key of a configuration file.

Each module ``bench/families/<family>.py`` gives what the benchmark needs
of one family of models, so that a family is added by adding a file:

    ring(cfg, mix, key)                the mix's seeded batches, made on
                                       the device in one call at set-up
    targets_per_batch(cfg, mix)        softmax targets in one batch
    hidden(p, batch, cfg, mode)        the plain float32 reference forward
                                       -> (h (T, d), labels (T,)), in the
                                       modes of ``bench/reference.py``
    head_table(p)                      the class embeddings of the params
    matmul_params(cfg)                 the backbone's matmul parameters
    flops_per_target(cfg, mix)         model FLOPs per target under the
                                       rule of ``bench/flops.py``
    GENERATORS                         the names of the traffic generators
                                       the module makes (a mix's
                                       ``generator``)
"""
from __future__ import annotations

import importlib
import pkgutil


def load(cfg: dict):
    """The family module of configuration ``cfg``."""
    return importlib.import_module(f"{__name__}.{cfg['family']}")


def with_generator(name: str):
    """The family module that makes the traffic generator ``name``."""
    for info in pkgutil.iter_modules(__path__):
        module = importlib.import_module(f"{__name__}.{info.name}")
        if name in getattr(module, "GENERATORS", ()):
            return module
    raise ValueError(f"no family makes the traffic generator {name!r}")
