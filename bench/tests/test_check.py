"""The output check fails what it exists to catch, at a size a test holds.

Runs of a cell at 4,096 passages on the CPU, through the whole harness with
its look for a chip skipped: a sound run is correct; the control (the
reference one precision step lower, int4, in the program's place) is not;
and each fault a serving cell can have, planted in the timed path, makes
``correct`` come out false.  The limits are the configurations' own.
"""

import copy
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import catalog, check, harness

N_DOCS = 4096


def small(name):
    cell = catalog.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["corpus"]["n_docs"] = N_DOCS
    if "ivf" in cell.config["index"]:
        cell.config["index"]["ivf"] = [64, 32]
    cell.traffic.pool = 256
    cell.traffic.rate = 150.0
    cell.traffic.sample_rows = 64
    return cell


def run(name, fault=None, seed=2 ** 32 + 11):
    return harness.run_cell(name, seed, 1.0, False, t_start=time.perf_counter(),
                            require_chip=False, cell=small(name), fault=fault)


class _Broken:
    """The served index with its search answers bent by ``bend``."""

    def __init__(self, index, bend):
        self._index, self._bend = index, bend

    def __getattr__(self, name):
        return getattr(self._index, name)

    def search(self, queries, k, **kw):
        return self._bend(self._index, queries, k, **kw)


def answer_altered(engine):
    def bend(index, q, k, **kw):
        v, i = index.search(q, k, **kw)
        return v, (np.asarray(i) + 1) % N_DOCS
    engine.index = _Broken(engine.index, bend)


def half_the_batch_left_out(engine):
    """Only the first half of each micro-batch is searched; its answers
    stand in for the rest."""
    def bend(index, q, k, **kw):
        q = np.asarray(q)
        half = max(1, q.shape[0] // 2)
        v, i = index.search(q[:half], k, **kw)
        reps = -(-q.shape[0] // half)
        return (np.tile(np.asarray(v), (reps, 1))[: q.shape[0]],
                np.tile(np.asarray(i), (reps, 1))[: q.shape[0]])
    engine.index = _Broken(engine.index, bend)


def routing_to_wrong_lists(engine):
    index = engine.index
    perm = np.random.default_rng(0).permutation(index.centroids.shape[0])
    index.centroids = jnp.asarray(index.centroids)[perm]


@pytest.mark.parametrize("name", ["dpr2m-int8.poisson",
                                  "dpr2m-int8-ivf.bulk"])
def test_a_sound_run_is_correct(name):
    assert run(name)["correct"] is True


@pytest.mark.parametrize("name,fault", [
    ("dpr2m-int8.poisson", answer_altered),
    ("dpr2m-int8.poisson", half_the_batch_left_out),
    ("dpr2m-int8-ivf.bulk", answer_altered),
    ("dpr2m-int8-ivf.bulk", half_the_batch_left_out),
    ("dpr2m-int8-ivf.bulk", routing_to_wrong_lists),
])
def test_a_fault_in_the_timed_path_is_not_correct(name, fault):
    result = run(name, fault)
    assert result["correct"] is False, result["check"]


@pytest.mark.parametrize("name", ["dpr2m-int8.poisson",
                                  "dpr2m-int8-ivf.bulk"])
def test_the_control_is_not_correct(name):
    """int4 codes in the program's place fail a limit that the program's
    own answers keep."""
    cell = small(name)
    t0 = time.perf_counter()
    session = harness.Session(cell, 7, t_start=t0, require_chip=False)
    try:
        result = session.window(1.0, False, t_start=t0)
    finally:
        session.close()
    q, ids, scores = session.checked_rows(result)
    limits = cell.config["check"]
    ref = session.reference()
    sound = check.compare(q, ids, scores, ref, cell.traffic.k, limits)
    sound["lost"] = 0.0
    assert check.verdict(sound, limits), sound
    low_scores, low_ids = session.reference(levels=15).topk(q, cell.traffic.k)
    control = check.compare(q, low_ids, low_scores, ref, cell.traffic.k,
                            limits)
    control["lost"] = 0.0
    assert not check.verdict(control, limits), control
