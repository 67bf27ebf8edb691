"""Traffic driver ``geo_loop``: rounds of every site's ``update()`` and
the master's merge, back to back.

A traffic file that names this driver (``"driver": "geo_loop"``) gives:

``sites``
    the sites of the deployment; the configuration's ``sns`` block must
    say the same (``SnsConfig.sites``);
``pool_chunks``, ``chunk_points``
    each site's host-memory pool of chunks.  Every site draws its own
    mixture from ``(--seed, site)``: its own cluster centres, with the
    configuration's cluster width and background share, as different
    biopsies would (``snsbench/data.py`` makes the chunks);
``fold_calls``
    rounds folded in set-up (the first compiles the fold and the merge);
``merge_every``
    the master merges after every this many rounds.

A round is one ``update()`` call with each site's next pool chunk: the
service folds each site's chunk on the device that holds that site's
fold.  The window runs rounds back to back; a round begun inside the
window is finished and counted, and its ``points`` are the points of
every site.  The host spans ``update`` and ``merge`` time each call.

The check serves the master's top-K from the merged view
(``SnsService.merge()``) and compares it with an exact count of every
site's chunks fed (``reference.heavy_hitter_numbers``), and adds
``site_points_gap`` (the largest per-site |counted - fed|) and
``merge_gap`` (the largest |merged table - sum of the site tables|, in
float64).

``CONTROL`` and ``FAULTS`` are the variants the check has to catch.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from snsbench import data, reference, sut

SPAN_NAMES = ("update", "merge")
SITE_WORD = 0x6E0      # keys each site's centres apart from the stream's


def site_stream(mix: data.Mixture, seed: int, site: int) -> data.Stream:
    """Site ``site``'s stream: centres of its own from ``(seed, site)``;
    its chunks are drawn as sequence ``draw = site``."""
    stream = data.Stream(mix, seed)
    stream.centers = np.random.default_rng(np.random.SeedSequence(
        [int(seed), SITE_WORD, int(site)])).uniform(
            data.BOX_MARGIN, 1.0 - data.BOX_MARGIN,
            size=(mix.n_clusters, mix.dims)).astype(np.float32)
    return stream


class Driver:
    def __init__(self, cell, seed: int, spans, variant=None, log=print):
        self.cell, self.seed, self.spans = cell, int(seed), spans
        self.variant, self.log = variant, log
        self.params = cell.traffic
        self.sites = int(self.params["sites"])
        if int(cell.config["sns"].get("sites", 1)) != self.sites:
            raise ValueError(f"traffic of {self.sites} sites on a "
                             f"configuration of "
                             f"{cell.config['sns'].get('sites', 1)}")
        self.mix = data.Mixture.from_config(cell.config["data"])
        self.rounds = 0
        self.per_round = []        # (update_s, merge_s) of window rounds

    def feed(self):
        """One round: every site's next pool chunk, then the merge every
        ``merge_every`` rounds."""
        i = self.rounds % int(self.params["pool_chunks"])
        with self.spans("update"):
            self.svc.update([pool[i] for pool in self.pools])
        self.fed[:, i] += 1
        self.rounds += 1
        if self.rounds % int(self.params["merge_every"]) == 0:
            with self.spans("merge"):
                self.svc.merge()

    def setup(self):
        p = self.params
        # the service first: a program without sites fails here, at once
        self.cfg, self.svc = sut.build(self.cell.config, self.seed,
                                       self.mix.dims, self.variant)
        t0 = time.perf_counter()
        n, k = int(p["chunk_points"]), int(p["pool_chunks"])
        streams = [site_stream(self.mix, self.seed, s)
                   for s in range(self.sites)]
        with ThreadPoolExecutor(min(32, os.cpu_count() or 1)) as ex:
            flat = list(ex.map(lambda sc: streams[sc[0]].chunk(
                sc[1], n, draw=sc[0]),
                [(s, c) for s in range(self.sites) for c in range(k)]))
        self.pools = [flat[s * k:(s + 1) * k] for s in range(self.sites)]
        self.fed = np.zeros((self.sites, k), np.int64)
        self.log(f"data: {self.sites} sites x {k} chunks of {n:,} "
                 f"{self.mix.dims}-D points, "
                 f"{time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        for _ in range(int(p["fold_calls"])):
            self.feed()
        self.log(f"fold: {self.rounds} rounds, "
                 f"{time.perf_counter() - t0:.3f} s")
        self.spans.events.clear()

    def window(self, t0: float, seconds: float) -> dict:
        first = self.rounds
        while True:
            n0 = len(self.spans.events)
            self.feed()
            took = {name: t1 - ts for name, ts, t1 in self.spans.events[n0:]}
            self.per_round.append([took["update"], took.get("merge", 0.0)])
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        rounds = self.rounds - first
        per = np.array(self.per_round)
        merges = self.spans.durations("merge")
        out = {"wall_s": wall, "attempted": rounds, "failed": 0,
               "points": rounds * self.sites * int(self.params["chunk_points"]),
               "sites": self.sites,
               "update_s_median": float(np.median(per[:, 0])),
               "update_s_max": float(np.max(per[:, 0])),
               "merges": len(merges)}
        if merges:
            out["merge_s_median"] = float(np.median(merges))
            out["merge_s_max"] = float(np.max(merges))
        return out

    def check(self) -> dict:
        svc, cfg = self.svc, self.cfg
        view = svc.merge()
        live = np.asarray(view.hh.mask).astype(bool)
        keys = (np.asarray(view.hh.key_hi, np.uint64)[live]
                << np.uint64(32)) | np.asarray(view.hh.key_lo, np.uint64)[live]
        est = np.asarray(view.hh.count, np.float64)[live]
        bound = float(svc.error_bound())
        site_points = np.asarray(svc.state.count, np.float64)
        merge_gap = float(np.max(np.abs(
            np.asarray(view.merged.table, np.float64)
            - np.asarray(svc.state.sketch.table, np.float64).sum(axis=0))))
        points = float(view.total_count)
        self.svc = None
        n = int(self.params["chunk_points"])
        fed = self.fed.sum(axis=1) * n
        uniq, counts = reference.exact_counts(
            [c for pool in self.pools for c in pool], self.fed.ravel(),
            cfg.bins)
        out = reference.heavy_hitter_numbers(
            keys, est, bound, uniq, counts, cfg.top_k, cfg.log2_cols)
        out["points_gap"] = abs(points - float(fed.sum()))
        out["site_points_gap"] = float(np.max(np.abs(site_points - fed)))
        out["merge_gap"] = merge_gap
        return out


# ------------------------------------------------- control and faults
def sketch_bf16(svc, cfg):
    """The control: every site's sketch table in bfloat16 in place of
    float32 (the program's own ``dtype`` option of ``stream.init``)."""
    import jax.numpy as jnp

    sk = svc.state.sketch
    svc.state = svc.state._replace(
        sketch=sk._replace(table=sk.table.astype(jnp.bfloat16)))


def _view(svc, cfg, table, sites):
    """A merged view of ``sites`` with ``table`` as the merged table."""
    import jax
    import jax.numpy as jnp
    from repro.core import geo
    from repro.core import heavy_hitters as hh_mod

    st = svc.state
    cands = jax.tree_util.tree_map(
        lambda x: x[sites].reshape((-1,) + x.shape[2:]), st.cands)
    sk = st.sketch._replace(table=table, params=jax.tree_util.tree_map(
        lambda p: p[0], st.sketch.params))
    return geo.GeoSketchResult(
        hh=hh_mod.from_candidates(sk, cands, cfg.top_k), merged=sk,
        total_count=jnp.sum(st.count[sites]),
        evict_max=jnp.sum(st.evict_max[sites]))


def merge_leaves_site_out(svc, cfg):
    """The master merges every site but the last."""
    import jax.numpy as jnp

    keep = np.arange(cfg.sites - 1)
    svc.merge = lambda: _view(
        svc, cfg, jnp.sum(svc.state.sketch.table[keep], axis=0), keep)


def merge_max(svc, cfg):
    """The master takes the sites' tables' maximum in place of their sum."""
    import jax.numpy as jnp

    every = np.arange(cfg.sites)
    svc.merge = lambda: _view(
        svc, cfg, jnp.max(svc.state.sketch.table, axis=0), every)


def site_not_folded(svc, cfg):
    """The last site's chunks are never folded."""
    fold = svc.update

    def update(site_chunks):
        site_chunks = list(site_chunks)
        site_chunks[-1] = site_chunks[-1][:0]
        return fold(site_chunks)
    svc.update = update


def reservoir_lowest(svc, cfg):
    """Each site's reservoir keeps its lowest-count cells: after each
    round every site's reservoir is replaced by the ``capacity`` cells of
    lowest count among its previous (faulty) reservoir and the chunk's
    exact runs, key-sorted as the program keeps it.  The sketches are
    folded as usual."""
    import jax
    import jax.numpy as jnp
    from repro.core import candidates

    fold = svc.update
    cap = svc.state.cands.key_hi.shape[1]
    pools = [{"keys": np.zeros(0, np.uint64), "counts": np.zeros(0)}
             for _ in range(cfg.sites)]

    def lowest(pool, chunk):
        keys = np.concatenate([pool["keys"],
                               reference.cell_keys(np.asarray(chunk),
                                                   cfg.bins)])
        ones = np.ones(keys.size - pool["keys"].size)
        uniq, inv = np.unique(keys, return_inverse=True)
        counts = np.bincount(inv, np.concatenate([pool["counts"], ones]))
        keep = np.sort(np.lexsort((uniq, counts))[:cap])
        pool["keys"], pool["counts"] = uniq[keep], counts[keep]
        pad = cap - keep.size
        fill = np.full(pad, candidates.INVALID_KEY, np.uint32)
        return (np.concatenate([(pool["keys"] >> np.uint64(32)
                                 ).astype(np.uint32), fill]),
                np.concatenate([(pool["keys"] & np.uint64(0xFFFFFFFF)
                                 ).astype(np.uint32), fill]),
                np.concatenate([pool["counts"], np.zeros(pad)]
                               ).astype(np.float32),
                np.arange(cap) < keep.size)

    def update(site_chunks):
        site_chunks = list(site_chunks)
        out = fold(site_chunks)
        cols = zip(*(lowest(pool, c) for pool, c in zip(pools, site_chunks)))
        hi, lo, count, mask = (np.stack(c) for c in cols)
        on = svc.state.cands.key_hi.sharding
        svc.state = svc.state._replace(cands=candidates.Candidates(
            key_hi=jax.device_put(hi, on), key_lo=jax.device_put(lo, on),
            count=jax.device_put(jnp.asarray(count), on),
            mask=jax.device_put(mask, on)))
        return out
    svc.update = update


CONTROL = sketch_bf16
FAULTS = [merge_leaves_site_out, merge_max, site_not_folded,
          reservoir_lowest]
