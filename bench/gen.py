"""The one generator every traffic file is read by.  A traffic file names
its loop and gives the parameters of its requests; everything random here
is drawn from the run's seed, so one seed gives one stream.

Serve traffic (``"loop": "serve"``, ``"arrival": "closed"``): the loop asks
for the next request of a cut as it needs one.  Each request asks a client
of that cut, drawn uniformly, for ``images_per_request`` samples at the
client's cut ``client_cuts[c]``, conditioned on a multi-hot vector over the
configuration's labels.  ``"labels": "unique"`` draws the vectors without
replacement per cut, so no (labels, cut) group repeats in a run.
"""
from __future__ import annotations

import numpy as np

SEED_WORDS = 4


def seed_words(seed: int) -> np.ndarray:
    """Four uint32 words from any non-negative integer seed."""
    return np.random.SeedSequence(int(seed)).generate_state(SEED_WORDS)


def label_vectors(n_classes: int) -> np.ndarray:
    """All 2**n_classes multi-hot vectors, row i the bits of i."""
    idx = np.arange(2 ** n_classes)[:, None]
    return ((idx >> np.arange(n_classes)) & 1).astype(np.float32)


class ServeStream:
    """A seeded request stream: ``take(cut)`` gives the next request of one
    cut."""

    def __init__(self, traffic: dict, n_classes: int, seed: int):
        if traffic["arrival"] != "closed":
            raise ValueError(f"unknown arrival {traffic['arrival']!r}")
        if traffic["labels"] != "unique":
            raise ValueError(f"unknown labels kind {traffic['labels']!r}")
        self.rng = np.random.default_rng(seed_words(seed))
        self.cuts = list(traffic["client_cuts"])
        self.batch = int(traffic["images_per_request"])
        self.vectors = label_vectors(n_classes)
        self._order = {c: list(self.rng.permutation(len(self.vectors)))
                       for c in sorted(set(self.cuts))}

    def take(self, cut: int) -> tuple:
        """(client, cut, y) of the next request at ``cut``."""
        clients = [c for c, k in enumerate(self.cuts) if k == cut]
        client = clients[int(self.rng.integers(len(clients)))]
        order = self._order[cut]
        if not order:
            raise RuntimeError(f"unique stream at cut {cut} exhausted")
        y = np.broadcast_to(self.vectors[order.pop(0)],
                            (self.batch, self.vectors.shape[1]))
        return client, cut, np.ascontiguousarray(y)


def count_repeats(requests) -> int:
    """Requests whose (labels, cut) group appeared before in ``requests``."""
    seen, repeats = set(), 0
    for _, cut, y in requests:
        key = (cut, np.asarray(y, np.float32).tobytes())
        repeats += key in seen
        seen.add(key)
    return repeats


def warm_vector(i: int, n_classes: int) -> np.ndarray:
    """Labels no traffic draws (every traffic vector is 0/1): warm-up
    requests then never share a group, nor a cached prefix, with traffic."""
    v = np.full((n_classes,), 0.5, np.float32)
    v[i % n_classes] = 0.1 + 0.05 * (i // n_classes)
    return v
