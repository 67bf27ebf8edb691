"""The system under test: an ``SnsService`` built from a configuration.

Every traffic driver builds its service here, from the configuration's
``preset`` (a name in ``repro.configs.sns_paper``) with every knob of its
``sns`` block restated, over the unit box of the configuration's data
dimensions, and reads what the service serves through ``served_heavy_hitters``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SEED_MOD = (1 << 30) - 1     # the program keys its PRNG with int32 seeds


def build(conf: dict, data_seed: int, dims: int, variant=None):
    """``(cfg, svc)``: the configuration ``conf`` (a ``configs/*.json``)
    as an ``SnsConfig`` seeded from ``data_seed``, and its service;
    ``variant(svc, cfg)`` (tests and the control script) may break or
    replace the service."""
    from repro.configs import sns_paper
    from repro.core.quantize import GridSpec
    from repro.core.service import ServiceConfig, SnsService

    cfg = dataclasses.replace(getattr(sns_paper, conf["preset"]),
                              seed=int(data_seed) % SEED_MOD, **conf["sns"])
    grid = GridSpec(dims=dims, bins=cfg.bins, lo=(0.0,) * dims,
                    hi=(1.0,) * dims)
    svc = SnsService(cfg, grid,
                     service_cfg=ServiceConfig(**conf.get("service", {})))
    if variant is not None:
        svc = variant(svc, cfg) or svc
    return cfg, svc


def served_heavy_hitters(svc, cfg):
    """``(keys, counts, bound)`` the service serves from its live state:
    the program's ``from_candidates`` top-K as uint64 cell keys, their
    estimated counts, and the served error bound."""
    from repro.core import heavy_hitters as hh_mod

    hh = hh_mod.from_candidates(svc.state.sketch, svc.state.cands,
                                cfg.top_k)
    live = np.asarray(hh.mask).astype(bool)
    keys = (np.asarray(hh.key_hi, np.uint64)[live] << np.uint64(32)) \
        | np.asarray(hh.key_lo, np.uint64)[live]
    est = np.asarray(hh.count, np.float64)[live]
    return keys, est, float(svc.error_bound())
