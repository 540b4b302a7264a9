"""Segment-sorted batched state encoding: bit-identity with the scalar
per-lane path over ragged random populations (hypothesis property test,
falling back to the deterministic tests/_shims shim), plus the flat
``sample_batch`` -> ``encode_sample_batch`` pipeline against real
simulator snapshots.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.state import (STATE_DIM, encode_cluster, encode_pair,
                              encode_sample_batch, encode_snapshot,
                              encode_snapshots)
from repro.sim import SlurmSimulator, sample_batch, synthesize_trace
from repro.sim.trace import V100

HOUR = 3600.0
LIMIT = 48 * HOUR


def make_sample(rng, nq, nr):
    return {
        "time": float(rng.uniform(0, 1e6)),
        "n_queued": nq,
        "queued_sizes": rng.integers(1, 9, nq),
        "queued_ages": rng.uniform(0, 7 * 24 * HOUR, nq),
        "queued_limits": rng.uniform(60.0, LIMIT, nq),
        "n_running": nr,
        "running_sizes": rng.integers(1, 9, nr),
        "running_elapsed": rng.uniform(0, LIMIT, nr),
        "running_limits": rng.uniform(60.0, LIMIT, nr),
        "n_free_nodes": 10,
        "utilization": 0.5,
    }


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 9), st.integers(0, 9)),
                min_size=1, max_size=6),
       st.integers(0, 2**31 - 1), st.booleans(), st.booleans())
def test_encode_snapshots_bit_identical(shape, seed, with_pred, with_succ):
    """encode_snapshots over ragged lanes — including empty queues and
    running sets — is bit-identical to per-lane encode_snapshot."""
    rng = np.random.default_rng(seed)
    samples = [make_sample(rng, nq, nr) for nq, nr in shape]
    B = len(samples)
    preds = None
    if with_pred:
        preds = [None if rng.random() < 0.3 else
                 {"size": int(rng.integers(1, 9)),
                  "limit": float(rng.uniform(60.0, LIMIT)),
                  "queue_time": float(rng.uniform(0, LIMIT)),
                  "elapsed": float(rng.uniform(0, LIMIT))}
                 for _ in range(B)]
    succs = None
    if with_succ:
        succs = [{"size": 1, "limit": LIMIT}] * B
    batch = encode_snapshots(samples, 88, LIMIT, preds, succs)
    assert batch.shape == (B, STATE_DIM)
    for b in range(B):
        ref = encode_snapshot(samples[b], 88, LIMIT,
                              preds[b] if preds else None,
                              succs[b] if succs else None)
        np.testing.assert_array_equal(batch[b], ref, err_msg=f"lane {b}")


def _random_pair(rng):
    return ({"size": int(rng.integers(1, 9)),
             "limit": float(rng.uniform(60.0, LIMIT)),
             "queue_time": float(rng.uniform(0, LIMIT)),
             "elapsed": float(rng.uniform(0, LIMIT))},
            {"size": int(rng.integers(1, 9)),
             "limit": float(rng.uniform(60.0, LIMIT))})


def _check_cluster_then_pair(nq, nr, seed, with_pred, with_succ):
    rng = np.random.default_rng(seed)
    sample = make_sample(rng, nq, nr)
    pred, succ = _random_pair(rng)
    pred = pred if with_pred else None
    succ = succ if with_succ else None
    ref = encode_snapshot(sample, 88, LIMIT, pred, succ)
    cluster = encode_cluster(sample, 88, LIMIT)
    assert cluster.dtype == np.float32 and cluster.shape == (STATE_DIM,)
    assert not cluster[34:].any()
    np.testing.assert_array_equal(cluster[:34], ref[:34])
    row = encode_pair(cluster.copy(), 88, LIMIT, pred, succ)
    assert row.tobytes() == ref.tobytes()
    # written over a row that held another chain's pair columns
    other = encode_pair(cluster.copy(), 88, LIMIT, *_random_pair(rng))
    assert encode_pair(other, 88, LIMIT, pred, succ).tobytes() \
        == ref.tobytes()


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 9), st.integers(0, 9), st.integers(0, 2**31 - 1),
       st.booleans(), st.booleans())
def test_encode_snapshot_is_cluster_then_pair(nq, nr, seed, with_pred,
                                              with_succ):
    """encode_snapshot equals the cluster part followed by the pair part,
    bit for bit, and the pair part gives the same row when written over
    a row that held another chain's pair columns."""
    _check_cluster_then_pair(nq, nr, seed, with_pred, with_succ)


@pytest.mark.parametrize("nq,nr,with_pred", [(0, 4, True), (4, 0, True),
                                             (0, 0, True), (3, 3, False),
                                             (0, 0, False)])
def test_encode_snapshot_is_cluster_then_pair_edges(nq, nr, with_pred):
    """The same identity on an empty queue, nothing running and no
    predecessor."""
    _check_cluster_then_pair(nq, nr, 7, with_pred, True)


def test_encode_snapshots_all_empty():
    rng = np.random.default_rng(0)
    samples = [make_sample(rng, 0, 0) for _ in range(3)]
    batch = encode_snapshots(samples, 88, LIMIT)
    for b in range(3):
        np.testing.assert_array_equal(
            batch[b], encode_snapshot(samples[b], 88, LIMIT))


def test_encode_snapshots_duplicate_values():
    """Ties in the percentile sorts must not break bit-identity."""
    sample = {
        "time": 0.0, "n_queued": 6,
        "queued_sizes": np.array([4, 4, 4, 4, 4, 4]),
        "queued_ages": np.array([0.0, 0.0, 10.0, 10.0, 10.0, 0.0]),
        "queued_limits": np.full(6, LIMIT),
        "n_running": 4,
        "running_sizes": np.array([2, 2, 2, 2]),
        "running_elapsed": np.zeros(4),
        "running_limits": np.full(4, 3600.0),
        "n_free_nodes": 1, "utilization": 0.9,
    }
    batch = encode_snapshots([sample, sample], 88, LIMIT)
    ref = encode_snapshot(sample, 88, LIMIT)
    np.testing.assert_array_equal(batch[0], ref)
    np.testing.assert_array_equal(batch[1], ref)


def test_sample_batch_flat_path_matches_dict_path():
    """repro.sim.sample_batch + encode_sample_batch on live simulators is
    bit-identical to sim.sample() + encode_snapshot per lane."""
    import copy
    jobs = synthesize_trace(V100, months=1, seed=2, load_scale=1.0)
    sims = []
    for frac in (0.2, 0.5, 0.8):
        sim = SlurmSimulator(V100.n_nodes, mode="fast")
        sim.load([copy.copy(j) for j in jobs])
        sim.run_until(jobs[0].submit_time
                      + frac * (jobs[-1].submit_time - jobs[0].submit_time))
        sims.append(sim)
    sb = sample_batch(sims)
    preds = np.array([[1.0, LIMIT, 120.0, 60.0]] * len(sims))
    succs = np.array([[1.0, LIMIT]] * len(sims))
    flat = encode_sample_batch(sb, V100.n_nodes, LIMIT, preds, succs)
    for i, sim in enumerate(sims):
        ref = encode_snapshot(sim.sample(), V100.n_nodes, LIMIT,
                              {"size": 1, "limit": LIMIT,
                               "queue_time": 120.0, "elapsed": 60.0},
                              {"size": 1, "limit": LIMIT})
        np.testing.assert_array_equal(flat[i], ref, err_msg=f"sim {i}")


def test_encode_sample_batch_preallocated_out():
    rng = np.random.default_rng(1)
    samples = [make_sample(rng, 3, 2), make_sample(rng, 0, 5)]
    from repro.core.state import _flatten_samples
    sb = _flatten_samples(samples)
    out = np.full((2, STATE_DIM), -1.0, np.float32)
    ret = encode_sample_batch(sb, 88, LIMIT, out=out)
    assert ret is out
    np.testing.assert_array_equal(out, encode_snapshots(samples, 88, LIMIT))
