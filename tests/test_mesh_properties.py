"""Property tests for the mesh-sharding contract (host-side, no devices).

Three invariants back the multi-device determinism matrix in
``tests/test_mesh_dse.py``:

  * ``shard_pad``/``shard_unpad`` round-trip for any (B, shard count) and
    pad rows are throwaway replicas of row 0,
  * Pareto-front ranking is permutation-invariant — the algebraic reason a
    sharded batch (any partition + merge order of the candidate axis)
    yields the same front as the serial scan,
  * ``remesh_search_state(state, N -> M -> N)`` is the identity: NSGA-II
    checkpoint state carries nothing shaped by the mesh.

Properties run under hypothesis when installed (``hypothesis_compat``
makes them skip cleanly otherwise); example twins alongside always run.
"""

import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core.search import (Dim, DesignSpace, NSGA2Search, SearchSpec,
                               constrained_non_dominated_sort,
                               remesh_search_state)
from repro.launch.mesh import MeshSpec, padded_size, shard_pad, shard_unpad


# --------------------------------------------------------------------------
# shard-pad / unpad round-trips
# --------------------------------------------------------------------------

def _check_roundtrip(b, k, m=3, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(b, m))
    p = shard_pad(a, k)
    assert p.shape[0] == padded_size(b, k)
    assert p.shape[0] % k == 0
    np.testing.assert_array_equal(shard_unpad(p, b), a)
    if p.shape[0] > b:      # every pad row replicates row 0
        np.testing.assert_array_equal(p[b:], np.broadcast_to(a[0], (p.shape[0] - b, m)))


@settings(max_examples=60, deadline=None)
@given(b=st.integers(min_value=1, max_value=64),
       k=st.integers(min_value=1, max_value=16),
       seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_pad_unpad_roundtrip_property(b, k, seed):
    _check_roundtrip(b, k, seed=seed)


def test_pad_unpad_roundtrip_examples():
    for b, k in ((1, 8), (7, 8), (21, 2), (16, 8), (8, 8), (1, 1)):
        _check_roundtrip(b, k)
    # divisible batches are returned untouched (no copy, no-op)
    a = np.arange(12.0).reshape(6, 2)
    assert shard_pad(a, 3) is a
    # 1-D candidate arrays (wire_bits, pipe, depth) pad on axis 0 too
    v = np.arange(5.0)
    np.testing.assert_array_equal(shard_unpad(shard_pad(v, 4), 5), v)
    # candidate axis other than 0 (stage-4 svc arrives [m, B])
    np.testing.assert_array_equal(
        shard_unpad(shard_pad(a.T, 4, axis=1), 6, axis=1), a.T)


def test_padded_size_rejects_zero_shards():
    with pytest.raises(ValueError, match="must be >= 1"):
        padded_size(8, 0)


# --------------------------------------------------------------------------
# Pareto ranking is permutation-invariant (sharded == serial fronts)
# --------------------------------------------------------------------------

def _check_permutation_invariance(objs, viol, perm):
    ranks = constrained_non_dominated_sort(objs, viol)
    ranks_p = constrained_non_dominated_sort(objs[perm], viol[perm])
    np.testing.assert_array_equal(ranks_p, ranks[perm])
    # front *membership* (what the DSE reads off rank 0) is order-free
    assert sorted(map(tuple, objs[ranks == 0])) == \
           sorted(map(tuple, objs[perm][ranks_p == 0]))


@settings(max_examples=60, deadline=None)
@given(n=st.integers(min_value=1, max_value=40),
       seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_pareto_rank_permutation_invariant_property(n, seed):
    rng = np.random.default_rng(seed)
    # coarse grid => plenty of ties/duplicates, the hard case for sorters
    objs = rng.integers(0, 5, size=(n, 2)).astype(float)
    viol = np.where(rng.random(n) < 0.3, rng.random(n), 0.0)
    _check_permutation_invariance(objs, viol, rng.permutation(n))


def test_pareto_rank_permutation_invariant_example():
    objs = np.array([[1.0, 4.0], [2.0, 2.0], [4.0, 1.0],
                     [2.0, 2.0], [3.0, 3.0], [5.0, 5.0]])
    viol = np.array([0.0, 0.0, 0.0, 0.0, 0.5, 0.0])
    _check_permutation_invariance(objs, viol, np.array([5, 3, 0, 2, 4, 1]))


# --------------------------------------------------------------------------
# remesh(state, N -> M -> N) is the identity on checkpoint state
# --------------------------------------------------------------------------

def _searched_state(generations=3):
    """Real engine state: a tiny pure-NumPy search driven to ``generations``."""
    space = DesignSpace((Dim("a", (1, 2, 3, 4)), Dim("b", (8, 16, 32))))
    eng = NSGA2Search(space, SearchSpec(population=8, generations=generations,
                                        seed=11))
    while not eng.done:
        asked = eng.ask()
        eng.tell({g: ((float(sum(g)), float(g[0] * g[1])), 0.0)
                  for g in asked})
    return eng


def _assert_state_equal(a, b, *, compare_mesh=True):
    tree_a, extra_a = a
    tree_b, extra_b = b
    assert sorted(tree_a) == sorted(tree_b)
    for key in tree_a:
        np.testing.assert_array_equal(tree_a[key], tree_b[key])
    if not compare_mesh:
        extra_a = {k: v for k, v in extra_a.items() if k != "mesh"}
        extra_b = {k: v for k, v in extra_b.items() if k != "mesh"}
    assert extra_a == extra_b


@settings(max_examples=25, deadline=None)
@given(n=st.integers(min_value=1, max_value=64),
       m=st.integers(min_value=1, max_value=64))
def test_remesh_roundtrip_identity_property(n, m):
    tree, extra = _searched_state().state()
    start = remesh_search_state(tree, extra, MeshSpec(devices=n))
    via_m = remesh_search_state(*start, MeshSpec(devices=m))
    back = remesh_search_state(*via_m, MeshSpec(devices=n))
    _assert_state_equal(back, start)                    # N -> M -> N identity
    _assert_state_equal(via_m, start, compare_mesh=False)  # arrays never move


def test_remesh_roundtrip_identity_example():
    eng = _searched_state()
    tree, extra = eng.state()
    start = remesh_search_state(tree, extra, MeshSpec(devices=8))
    assert start[1]["mesh"] == {"devices": 8, "scenario_axis": 1}
    via2 = remesh_search_state(*start, MeshSpec(devices=2))
    assert via2[1]["mesh"] == {"devices": 2, "scenario_axis": 1}
    back = remesh_search_state(*via2, MeshSpec(devices=8))
    _assert_state_equal(back, start)
    # the remeshed state restores to an engine whose next RNG draws (and
    # archive) match the original bit-for-bit
    restored = NSGA2Search.from_state(eng.space, eng.spec, *back)
    assert restored.archive() == eng.archive()
    assert restored.hv_history == eng.hv_history
    np.testing.assert_array_equal(restored.rng.random(16), eng.rng.random(16))
    # dropping the stamp entirely (mesh=None) also restores cleanly
    bare = remesh_search_state(tree, extra, None)
    assert "mesh" not in bare[1]
    _assert_state_equal(bare, (tree, extra), compare_mesh=False)
