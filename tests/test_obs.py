"""Host spans and counters of the ingest path (``repro.obs``) and the
device scopes of the fold.

Totals are process-wide, so each test reads the change across what it
does.  The scopes are checked where they land: in the ``op_name``
metadata of the compiled program, which is what a device trace names an
op by."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import pipeline, quantize, stream
from repro.core.service import SnsService

GRID = quantize.GridSpec(dims=3, bins=8, lo=(0.0,) * 3, hi=(1.0,) * 3)
FOLD_SCOPES = {"sns.fold.keys", "sns.fold.sort", "sns.fold.scatter",
               "sns.fold.merge"}
INGEST_SPANS = ("sns.ingest.assemble", "sns.ingest.put",
                "sns.ingest.dispatch", "sns.update.wait")
COUNTERS = ("sns.ingest.points", "sns.ingest.rows",
            "sns.ingest.superbatches", "sns.ingest.put_bytes")


def _cfg(superbatch):
    return pipeline.SnsConfig(bins=8, rows=4, log2_cols=10, top_k=16,
                              candidate_pool=64, ingest_chunk=256,
                              ingest_superbatch=superbatch, seed=0)


def _delta(before, after):
    """Per-span call counts and per-counter totals added between two
    snapshots."""
    calls = {k: v["count"] - before["spans"].get(k, {"count": 0})["count"]
             for k, v in after["spans"].items()}
    counts = {k: v - before["counters"].get(k, 0)
              for k, v in after["counters"].items()}
    return calls, counts


def _points(n, seed=0):
    return np.random.default_rng(seed).uniform(
        0, 1, (n, 3)).astype(np.float32)


def test_span_and_count_add_to_totals():
    before = obs.snapshot()
    with obs.span("sns.test.block"):
        pass
    with obs.span("sns.test.block"):
        pass
    obs.count("sns.test.items", 3)
    obs.count("sns.test.items")
    after = obs.snapshot()
    calls, counts = _delta(before, after)
    assert calls["sns.test.block"] == 2
    assert counts["sns.test.items"] == 4
    assert after["spans"]["sns.test.block"]["seconds"] >= \
        before["spans"].get("sns.test.block", {"seconds": 0.0})["seconds"]


def test_span_counts_a_block_that_raises():
    before = obs.snapshot()
    with pytest.raises(ValueError):
        with obs.span("sns.test.raises"):
            raise ValueError("x")
    calls, _ = _delta(before, obs.snapshot())
    assert calls["sns.test.raises"] == 1


@pytest.mark.parametrize("superbatch", [1, 2], ids=["chunk", "superbatch"])
@pytest.mark.parametrize("n", [2048, 1000], ids=["whole", "ragged"])
def test_update_spans_and_counters(superbatch, n):
    svc = SnsService(_cfg(superbatch), GRID)
    before = obs.snapshot()
    svc.update(_points(n))
    calls, counts = _delta(before, obs.snapshot())
    rows = -(-n // (256 * superbatch)) * 256 * superbatch
    dispatches = rows // (256 * superbatch)
    assert counts["sns.ingest.points"] == n
    assert counts["sns.ingest.rows"] == rows
    if n % (256 * superbatch) == 0:
        assert counts["sns.ingest.rows"] == counts["sns.ingest.points"]
    else:
        assert counts["sns.ingest.rows"] > counts["sns.ingest.points"]
    assert counts["sns.ingest.superbatches"] == dispatches
    # float32 points and one bool mask byte per row
    assert counts["sns.ingest.put_bytes"] == rows * (3 * 4 + 1)
    assert calls["sns.ingest.put"] == dispatches
    assert calls["sns.ingest.dispatch"] == dispatches
    assert calls["sns.ingest.assemble"] == dispatches + 1   # the last: none
    assert calls["sns.update.wait"] == 2     # before and after the fold
    assert float(svc.state.count) == n


def test_health_reports_ingest_snapshot():
    svc = SnsService(_cfg(2), GRID)
    svc.update(_points(600))
    ingest = svc.health()["ingest"]
    assert set(INGEST_SPANS) <= set(ingest["spans"])
    assert set(COUNTERS) <= set(ingest["counters"])
    assert all(v["seconds"] >= 0 and v["count"] >= 1
               for v in ingest["spans"].values())


def _op_name_scopes(hlo_text):
    return set(re.findall(r'op_name="[^"]*?(sns\.fold\.[a-z]+)', hlo_text))


def test_compiled_superbatch_carries_the_four_fold_scopes():
    state = stream.init(jax.random.key(0), 4, 8, 16)
    text = stream.ingest_superbatch.lower(
        state, jnp.zeros((2, 256, 3)), jnp.ones((2, 256), bool),
        grid=GRID).compile().as_text()
    assert _op_name_scopes(text) == FOLD_SCOPES


def test_merge_states_carries_the_merge_scope():
    a = stream.init(jax.random.key(0), 4, 8, 16)
    b = stream.ingest_all(stream.init(jax.random.key(0), 4, 8, 16), GRID,
                          [_points(300)], 128)
    text = jax.jit(stream.merge_states).lower(a, b).compile().as_text()
    assert "sns.fold.merge" in _op_name_scopes(text)
