"""KGE substrate: score functions, training, eval, virtual-table invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kge.data import corrupt_triples, synthesize_universe
from repro.kge.eval import link_prediction, triple_classification_accuracy
from repro.kge.models import (
    KGEModel,
    MODEL_FAMILIES,
    init_kge,
    margin_loss,
    normalize_entities,
    score_all_heads,
    score_all_tails,
    score_triples,
)
from repro.kge.trainer import KGETrainer


@pytest.fixture(scope="module")
def small_kgs():
    stats = [("A", 10, 80000, 280000), ("B", 8, 60000, 200000)]
    aligns = [("A", "B", 20000)]
    return synthesize_universe(seed=0, scale=1 / 400, kg_stats=stats, alignments=aligns)


@pytest.mark.parametrize("family", MODEL_FAMILIES)
def test_score_finite_all_families(family):
    m = KGEModel(family, num_entities=50, num_relations=5, dim=16)
    p = init_kge(jax.random.PRNGKey(0), m)
    h = jnp.array([0, 1, 2])
    r = jnp.array([0, 1, 2])
    t = jnp.array([3, 4, 5])
    s = score_triples(p, m, h, r, t)
    assert s.shape == (3,)
    assert jnp.isfinite(s).all()


def test_score_all_matches_pointwise():
    m = KGEModel("transe", 40, 4, 8)
    p = init_kge(jax.random.PRNGKey(1), m)
    h = jnp.array([0, 5])
    r = jnp.array([1, 2])
    full = score_all_tails(p, m, h, r)
    for j, t in enumerate([7, 13]):
        s = score_triples(p, m, h[j : j + 1], r[j : j + 1], jnp.array([t]))
        assert jnp.allclose(full[j, t], s[0], atol=1e-5)
    fullh = score_all_heads(p, m, r, jnp.array([7, 13]))
    s = score_triples(p, m, jnp.array([3]), r[:1], jnp.array([7]))
    assert jnp.allclose(fullh[0, 3], s[0], atol=1e-5)


def test_margin_loss_zero_when_separated():
    pos = jnp.array([10.0, 10.0])
    neg = jnp.array([0.0, 0.0])
    assert float(margin_loss(pos, neg, 4.0)) == 0.0
    assert float(margin_loss(neg, pos, 4.0)) == 14.0


def test_normalize_entities_unit_ball():
    m = KGEModel("transe", 30, 3, 8)
    p = init_kge(jax.random.PRNGKey(0), m)
    p = dict(p, ent=p["ent"] * 100)
    p = normalize_entities(p)
    norms = jnp.linalg.norm(p["ent"], axis=-1)
    assert float(norms.max()) <= 1.0 + 1e-5


def test_training_reduces_loss_and_beats_untrained(small_kgs):
    kg = small_kgs["A"]
    tr = KGETrainer(kg, "transe", dim=32, seed=0, margin=2.0)
    first = tr.train_epochs(5)
    for _ in range(5):
        last = tr.train_epochs(25)
    assert last < first * 0.7
    acc = triple_classification_accuracy(tr.params, tr.model, kg)
    untrained = KGETrainer(kg, "transe", dim=32, seed=9, margin=2.0)
    acc0 = triple_classification_accuracy(untrained.params, untrained.model, kg)
    assert acc > acc0 + 0.05


def test_link_prediction_metrics_sane(small_kgs):
    kg = small_kgs["B"]
    tr = KGETrainer(kg, "transe", dim=32, seed=0, margin=2.0)
    tr.train_epochs(100)
    lp = link_prediction(tr.params, tr.model, kg, max_test=60)
    assert 1.0 <= lp["mean_rank"] <= kg.num_entities
    assert 0.0 <= lp["hit@1"] <= lp["hit@3"] <= lp["hit@10"] <= 1.0


def test_corrupt_triples_changes_one_side():
    rng = np.random.default_rng(0)
    tri = np.array([[1, 0, 2]] * 100, dtype=np.int32)
    neg = corrupt_triples(rng, tri, 50)
    changed_h = neg[:, 0] != 1
    changed_t = neg[:, 2] != 2
    assert ((changed_h & ~changed_t) | (~changed_h & changed_t) |
            (~changed_h & ~changed_t)).all()  # at most one side corrupted
    assert (neg[:, 1] == 0).all()


def test_virtual_extension_roundtrip(small_kgs):
    kg = small_kgs["A"]
    tr = KGETrainer(kg, "transe", dim=16, seed=0)
    e0, r0 = tr.model.num_entities, tr.model.num_relations
    v_ent = jnp.ones((5, 16)) * 0.1
    v_rel = jnp.ones((2, 16)) * 0.2
    extra = np.array([[e0, r0, 3], [1, r0 + 1, e0 + 4]], dtype=np.int64)
    tr.extend_tables(v_ent, v_rel, extra)
    assert tr.model.num_entities == e0 + 5
    assert tr.params["ent"].shape[0] == e0 + 5
    tr.train_epochs(1)  # trains with the virtual triples
    tr.strip_virtual()
    assert tr.model.num_entities == e0
    assert tr.params["ent"].shape[0] == e0


def test_universe_alignment_consistency(small_kgs):
    a, b = small_kgs["A"], small_kgs["B"]
    ia, ib = a.aligned_with(b)
    assert len(ia) > 30
    assert (a.universe_ids[ia] == b.universe_ids[ib]).all()


def test_exact_universe_keeps_listed_sizes():
    stats = [("A", 10, 80000, 280000), ("B", 8, 60000, 200000)]
    kgs = synthesize_universe(seed=0, scale=1 / 400, kg_stats=stats,
                              alignments=[("A", "B", 4000)], exact=True)
    assert kgs["A"].num_entities == 200 and kgs["B"].num_entities == 150
    # 340 distinct entities: more than the default 0.8 × 350 universe holds
    assert len(kgs["A"].aligned_with(kgs["B"])[0]) == 10


@pytest.mark.parametrize("block_elems", [1 << 26, 3 * 97])
def test_nearest_tails_matches_numpy_scan(block_elems):
    """The blockwise device scan picks the tail the plain NumPy scan picks,
    for one block and for several with a padded tail block."""
    from repro.kge.data import _nearest_tails

    rng = np.random.default_rng(0)
    ent = rng.normal(size=(97, 12)).astype(np.float32)
    h = rng.integers(0, 97, 50)
    target = ent[h] + rng.normal(0, 0.6, (50, 12)).astype(np.float32)
    d = ((target[:, None, :].astype(np.float64) - ent[None]) ** 2).sum(-1)
    d[np.arange(50), h] = np.inf  # no self-loop
    got = _nearest_tails(target, h, ent, block_elems=block_elems)
    np.testing.assert_array_equal(got, d.argmin(axis=1))
