"""ETS core: tree accounting, REBASE weights, ILP, clustering, controllers."""
import numpy as np
import pytest

from repro.core import (ETSConfig, SearchConfig, SearchTree,
                        SelectionProblem, cluster_embeddings, ets_prune,
                        evaluate_method, greedy_select, milp_select,
                        rebase_reweight, rebase_weights, run_search,
                        weighted_majority)
from repro.core.synthetic import SyntheticProblem, SyntheticTaskConfig


# ---------------------------------------------------------------------------
# SearchTree
# ---------------------------------------------------------------------------

def build_tree():
    t = SearchTree(root_tokens=10)
    a = t.add(0, n_tokens=5)
    b = t.add(0, n_tokens=7)
    a1 = t.add(a, n_tokens=3)
    a2 = t.add(a, n_tokens=4)
    return t, (a, b, a1, a2)


def test_tree_kv_accounting():
    t, (a, b, a1, a2) = build_tree()
    assert t.nodes_for_leaves([a1, a2]) == {a, a1, a2}
    # shared: root 10 + a 5 + a1 3 + a2 4 = 22
    assert t.kv_tokens_for_leaves([a1, a2]) == 22
    # unshared: (10+5+3) + (10+5+4) = 37
    assert t.unshared_kv_tokens([a1, a2]) == 37
    assert t.kv_tokens_for_leaves([b]) == 17


def test_tree_path():
    t, (a, b, a1, a2) = build_tree()
    assert t.path(a1) == [a, a1]
    assert t.path_tokens(a1) == 18


# ---------------------------------------------------------------------------
# REBASE weights
# ---------------------------------------------------------------------------

def test_rebase_weights_exact_sum():
    w = rebase_weights([0.9, 0.5, 0.1], 16, temperature=0.2)
    assert w.sum() == 16
    assert w[0] > w[1] > w[2] >= 0


def test_rebase_weights_ceil_mode():
    w = rebase_weights([0.9, 0.5, 0.1], 16, temperature=0.2, exact=False)
    assert w.sum() >= 16          # paper's literal ceil can exceed N


def test_rebase_reweight_subset():
    r = [0.9, 0.5, 0.1, 0.7]
    w = rebase_reweight(r, [0, 3], 10)
    assert w.sum() == 10 and w.shape == (2,)
    assert w[0] > w[1]


def test_rebase_balanced_at_high_temperature():
    w = rebase_weights([0.9, 0.1], 10, temperature=100.0)
    assert abs(int(w[0]) - int(w[1])) <= 1


# ---------------------------------------------------------------------------
# ILP
# ---------------------------------------------------------------------------

def _problem(lambda_b=1.0, lambda_d=1.0, clusters=None):
    # two leaves share node "a"; leaf 2 is its own branch "b"
    return SelectionProblem(
        leaf_values=np.array([8.0, 6.0, 2.0]),
        leaf_paths=[["a", "l0"], ["a", "l1"], ["b", "l2"]],
        clusters=clusters, lambda_b=lambda_b, lambda_d=lambda_d)


def test_milp_prunes_divergent_low_value_branch():
    res = milp_select(_problem(lambda_b=1.0))
    # leaf 2 is low-value and requires 2 extra nodes -> pruned
    assert 2 not in res.selected
    assert 0 in res.selected


def test_milp_at_least_one():
    res = milp_select(SelectionProblem(
        leaf_values=np.array([0.1]), leaf_paths=[["a"]], lambda_b=100.0))
    assert res.selected == [0]


def test_milp_coverage_term_rescues_diverse_leaf():
    # without coverage leaf 2 is pruned; with it (own cluster) retained
    res0 = milp_select(_problem(lambda_b=1.0, clusters=None))
    assert 2 not in res0.selected
    res1 = milp_select(_problem(lambda_b=1.0, lambda_d=2.0,
                                clusters=np.array([0, 0, 1])))
    assert 2 in res1.selected


def test_greedy_matches_milp_on_simple_problems():
    rng = np.random.default_rng(0)
    agree = 0
    for _ in range(20):
        L = 6
        vals = rng.random(L) * 10
        paths = [[f"n{i//2}", f"l{i}"] for i in range(L)]
        prob = SelectionProblem(leaf_values=vals, leaf_paths=paths,
                                lambda_b=1.0)
        m = milp_select(prob)
        g = greedy_select(prob)
        agree += set(m.selected) == set(g.selected)
    assert agree >= 15   # greedy is near-optimal on small trees


def _brute_force_obj(prob, subset):
    W = prob.leaf_values
    Wsum = W.sum()
    nodes = set()
    for i in subset:
        nodes.update(prob.leaf_paths[i])
    all_nodes = {v for path in prob.leaf_paths for v in path}
    obj = sum(W[i] for i in subset) / Wsum \
        - prob.lambda_b * len(nodes) / len(all_nodes)
    if prob.clusters is not None:
        cl = set(prob.clusters[i] for i in subset)
        obj += prob.lambda_d * len(cl) / len(set(prob.clusters.tolist()))
    return obj


def test_milp_is_optimal_vs_bruteforce():
    """The ILP solution matches exhaustive enumeration (node coupling,
    coverage and |S|>=1 all correctly encoded)."""
    import itertools
    rng = np.random.default_rng(7)
    for trial in range(10):
        L = 6
        vals = rng.random(L) * 10
        shared = [f"n{i % 3}" for i in range(L)]
        paths = [[shared[i], f"l{i}"] for i in range(L)]
        clusters = rng.integers(0, 3, L)
        prob = SelectionProblem(
            leaf_values=vals, leaf_paths=paths, clusters=clusters,
            lambda_b=float(rng.random() * 2),
            lambda_d=float(rng.random() * 2))
        res = milp_select(prob)
        best = max((_brute_force_obj(prob, s)
                    for r in range(1, L + 1)
                    for s in itertools.combinations(range(L), r)))
        assert abs(_brute_force_obj(prob, res.selected) - best) < 1e-9


# ---------------------------------------------------------------------------
# Clustering
# ---------------------------------------------------------------------------

def test_clustering_recovers_groups():
    rng = np.random.default_rng(0)
    c0 = rng.normal(size=8)
    c1 = -c0
    embs = np.stack([c0 + rng.normal(scale=0.01, size=8) for _ in range(3)]
                    + [c1 + rng.normal(scale=0.01, size=8) for _ in range(3)])
    labels = cluster_embeddings(embs, threshold=0.3)
    assert len(set(labels[:3])) == 1
    assert len(set(labels[3:])) == 1
    assert labels[0] != labels[3]


def test_clustering_single_point():
    assert cluster_embeddings(np.ones((1, 4))).shape == (1,)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_single_linkage_matches_reference(seed):
    """SciPy single linkage cuts the same partition as the union-find
    reference (labels may be numbered differently)."""
    from repro.core.clustering import _single_linkage, cosine_distance_matrix
    embs = np.random.default_rng(seed).normal(size=(12, 6))
    got = cluster_embeddings(embs, threshold=0.6, method="single")
    want = _single_linkage(cosine_distance_matrix(embs), 0.6)
    pairs = lambda lab: {(i, j) for i in range(len(lab))
                         for j in range(len(lab)) if lab[i] == lab[j]}
    assert pairs(got) == pairs(want)
    assert 1 < len(set(want)) < len(want)    # some merges, not all


# ---------------------------------------------------------------------------
# ets_prune integration
# ---------------------------------------------------------------------------

def test_ets_prune_redundant_siblings():
    t = SearchTree(root_tokens=10)
    kids = [t.add(0, n_tokens=5) for _ in range(4)]
    rewards = [0.8, 0.79, 0.3, 0.78]
    # leaves 0,1,3 same cluster; leaf 2 its own
    embs = np.array([[1, 0], [1, 0.01], [0, 1], [1, -0.01]], float)
    cfg = ETSConfig(lambda_b=2.0, lambda_d=1.0)
    step = ets_prune(t, kids, rewards, 8, cfg, embs)
    assert len(step.selected) < 4          # something pruned
    assert step.counts.sum() == 8          # Eq.3 reallocates full budget


def test_weighted_majority():
    assert weighted_majority([("a", 0.6), ("b", 0.9), ("a", 0.5)]) == "a"
    assert weighted_majority([]) is None


def test_weighted_majority_tie_break_is_order_independent():
    """Regression: ties used to fall through to dict insertion order, so
    permuting the completed list could change the winner.  Ties now
    break on the answer sort key — the smallest tied answer wins no
    matter the arrival order."""
    import itertools
    pairs = [("b", 0.5), ("a", 0.3), ("c", 0.5), ("a", 0.2)]
    # a, b and c all sum to 0.5 -> the tie-break picks "a" always
    for perm in itertools.permutations(pairs):
        assert weighted_majority(list(perm)) == "a"
    # 3+ addends: naive left-to-right float accumulation makes both the
    # totals and tie membership depend on arrival order (0.1+0.2+0.3 !=
    # 0.3+0.2+0.1 in binary); the exactly-rounded per-answer reduction
    # keeps every permutation agreeing
    pairs = [("z", 0.1), ("z", 0.2), ("z", 0.3), ("a", 0.6)]
    winners = {weighted_majority(list(p))
               for p in itertools.permutations(pairs)}
    assert len(winners) == 1
    # negative weights clamp to zero and cannot break the tie either
    assert weighted_majority([("z", 0.4), ("y", 0.4), ("z", -1.0)]) == "y"
    # mixed answer types still order deterministically (by type name)
    for perm in itertools.permutations([(2, 0.5), ("2", 0.5)]):
        assert weighted_majority(list(perm)) == 2


# ---------------------------------------------------------------------------
# End-to-end search dynamics (the paper's Table 1/3 qualitative claims)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ets_matches_rebase_accuracy_with_less_kv():
    base = evaluate_method(SearchConfig(method="rebase", width=64),
                           n_problems=60, seed=21)
    ets = evaluate_method(
        SearchConfig(method="ets", width=64,
                     ets=ETSConfig(lambda_b=2.0, lambda_d=1.0)),
        n_problems=60, seed=21)
    assert ets["accuracy"] >= base["accuracy"] - 0.08
    assert ets["avg_kv_shared"] < base["avg_kv_shared"] / 1.5


@pytest.mark.slow
def test_diversity_term_protects_aggressive_compression():
    accs = {}
    for method in ["ets", "ets-kv"]:
        r = evaluate_method(
            SearchConfig(method=method, width=64,
                         ets=ETSConfig(lambda_b=4.0, lambda_d=1.0)),
            n_problems=80, seed=3)
        accs[method] = r["accuracy"]
    assert accs["ets"] >= accs["ets-kv"] + 0.05


def test_all_methods_run():
    for method in ["beam", "dvts", "rebase", "ets", "ets-kv"]:
        prob = SyntheticProblem(SyntheticTaskConfig(), seed=5)
        res = run_search(prob, SearchConfig(method=method, width=8),
                         tree=prob.make_tree())
        assert res.steps >= 1
        assert res.kv_summary["steps"] >= 1


# ---------------------------------------------------------------------------
# Property: tree KV accounting invariants under random tree growth
# ---------------------------------------------------------------------------

def test_tree_accounting_invariants_random():
    rng = np.random.default_rng(0)
    for _ in range(20):
        t = SearchTree(root_tokens=int(rng.integers(1, 50)))
        nodes = [0]
        for _ in range(int(rng.integers(1, 40))):
            parent = int(nodes[rng.integers(len(nodes))])
            nodes.append(t.add(parent, n_tokens=int(rng.integers(1, 60))))
        leaves = [n for n in nodes[1:] if not t.node(n).children]
        sel = [leaves[i] for i in
               rng.choice(len(leaves), size=min(5, len(leaves)),
                          replace=False)]
        shared = t.kv_tokens_for_leaves(sel)
        unshared = t.unshared_kv_tokens(sel)
        # sharing never exceeds per-sequence storage
        assert shared <= unshared
        # both bounded below by the longest single path
        assert shared >= max(t.path_tokens(l) for l in sel)
        # single leaf: shared == unshared == its path
        one = [sel[0]]
        assert t.kv_tokens_for_leaves(one) == t.unshared_kv_tokens(one) \
            == t.path_tokens(sel[0])
        # monotonicity: adding a leaf never decreases either measure
        if len(sel) > 1:
            assert t.kv_tokens_for_leaves(sel) >= \
                t.kv_tokens_for_leaves(sel[:-1])
