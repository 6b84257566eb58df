"""The traffic generator: one seed gives one stream, and the unique stream
never repeats a (labels, cut) group."""
import numpy as np
import pytest

from bench import common, gen

BIG = 2 ** 31 + 987_654_321


def _traffic(name, **kw):
    t = common.load_json(common.traffic_file(name))
    t.update(kw)
    return t


def _takes(t, seed, takes=40):
    s = gen.ServeStream(t, 8, seed)
    cuts = sorted(set(t["client_cuts"]))
    return [s.take(cuts[i % len(cuts)]) for i in range(takes)]


def test_same_seed_same_stream():
    t = _traffic("unique")
    flat = lambda reqs: [(cl, cut, y.tobytes()) for cl, cut, y in reqs]
    assert flat(_takes(t, BIG)) == flat(_takes(t, BIG))
    assert flat(_takes(t, BIG)) != flat(_takes(t, BIG + 1))


def test_unique_stream_never_repeats_a_group():
    t = _traffic("unique")
    reqs = _takes(t, BIG, takes=2 * 256)
    assert gen.count_repeats(reqs) == 0
    assert {cut for _, cut, _ in reqs} == set(t["client_cuts"])
    for client, cut, y in reqs:
        assert t["client_cuts"][client] == cut
        assert y.shape == (t["images_per_request"], 8)
        assert set(np.unique(y)) <= {0.0, 1.0}


def test_unique_stream_runs_out_rather_than_repeat():
    t = _traffic("unique")
    s = gen.ServeStream(t, 2, BIG)
    for _ in range(4):
        s.take(200)
    with pytest.raises(RuntimeError, match="exhausted"):
        s.take(200)


def test_every_client_of_a_cut_is_drawn():
    t = _traffic("unique")
    clients = {c for c, _, _ in _takes(t, BIG, takes=200)}
    assert clients == set(range(len(t["client_cuts"])))


@pytest.mark.parametrize("key,value", [("labels", "zipf"),
                                       ("arrival", "poisson")])
def test_unknown_kinds_are_refused(key, value):
    with pytest.raises(ValueError):
        gen.ServeStream(_traffic("unique", **{key: value}), 8, 1)


def test_count_repeats_counts_a_repeat():
    y = np.ones((4, 8), np.float32)
    assert gen.count_repeats([(0, 200, y), (1, 100, y), (0, 200, y)]) == 1


def test_warm_labels_are_not_traffic_labels():
    vecs = {v.tobytes() for v in gen.label_vectors(8)}
    warm = {gen.warm_vector(i, 8).tobytes() for i in range(64)}
    assert len(warm) == 64 and not warm & vecs
