"""The benchmark's own generators: shapes fixed by the configuration, the
on-disk cache, and traffic of a fixed size."""
import numpy as np

from bench.gen import traffic, universe

PAIR = {"kind": "synthesize",
        "owners": [["Big", 12, 600, 1500], ["Small", 3, 400, 500]],
        "alignments": [["Small", "Big", 120]]}


def test_two_seeds_give_the_configured_counts_and_shapes():
    a = universe.build(PAIR, seed=1)
    b = universe.build(PAIR, seed=2)
    for name, rel, ent, tri in PAIR["owners"]:
        for g in (a, b):
            o = g[name]
            assert int(o["num_entities"]) == ent and int(o["num_relations"]) == rel
            n = len(o["train"]) + len(o["valid"]) + len(o["test"])
            assert n == tri
            all_t = np.concatenate([o["train"], o["valid"], o["test"]])
            assert len(np.unique(all_t, axis=0)) == tri      # deduped, topped up
            assert all_t[:, 0].max() < ent and all_t[:, 1].max() < rel
        assert {k: v.shape for k, v in a[name].items()} == \
            {k: v.shape for k, v in b[name].items()}
    for g in (a, b):
        common = np.intersect1d(g["Big"]["universe_ids"], g["Small"]["universe_ids"])
        assert len(common) == 120
    assert not np.array_equal(a["Big"]["train"], b["Big"]["train"])


def test_equal_shape_owners_match():
    g = universe.build({"kind": "equal_shape", "owners": 4, "entities": 50,
                        "relations": 3, "triples": 200, "shared": 10}, seed=3)
    shapes = {n: {k: v.shape for k, v in o.items()} for n, o in g.items()}
    assert len({str(s) for s in shapes.values()}) == 1
    assert len(np.intersect1d(g["K0"]["universe_ids"], g["K3"]["universe_ids"])) == 10


def test_cache_hits_on_the_second_load_and_misses_on_a_new_generator(tmp_path, monkeypatch):
    first, built1 = universe.load(PAIR, 5, cache_dir=tmp_path)
    again, built2 = universe.load(PAIR, 5, cache_dir=tmp_path)
    assert built1 > 0.0 and built2 == 0.0
    for name in first:
        for k in first[name]:
            assert np.array_equal(first[name][k], again[name][k])
    monkeypatch.setattr(universe, "source_hash", lambda: "changed-generator")
    _, built3 = universe.load(PAIR, 5, cache_dir=tmp_path)
    assert built3 > 0.0


def test_traffic_has_the_same_amount_of_work_for_every_seed():
    test = np.arange(300).reshape(100, 3)
    p = {"rate": 250, "rank_share": 0.5, "zipf": 0.99}
    a = traffic.open_loop(p, test, seed=1, seconds=2.0)
    b = traffic.open_loop(p, test, seed=2**31 + 9, seconds=2.0)
    for g in (a, b):
        assert len(g["at"]) == 500 and int((g["kind"] == 0).sum()) == 250
        assert np.all(np.diff(g["at"]) >= 0) and g["at"][-1] < 2.0
    assert not np.array_equal(a["h"], b["h"])
