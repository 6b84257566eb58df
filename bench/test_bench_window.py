"""The serve loop's window and check sample without the program: the
window runs from poll to poll and counts every request the polls in
between retired; the sample holds one whole wave per cut and a request of
every client of that cut."""
import time
import types

import numpy as np
import pytest

from bench import gen
from bench.loops import serve

TRAFFIC = {"client_cuts": [200, 100, 100], "backlog_waves": 1,
           "max_wave": 2, "images_per_request": 4, "arrival": "closed",
           "labels": "unique"}


class BunchedDriver:
    """Retires the oldest admitted requests two waves at a time, on every
    third poll, each poll taking ``poll_s``."""

    def __init__(self, poll_s=0.004):
        self.poll_s, self.polls, self.admitted = poll_s, 0, []
        self.pending = []

    def submit(self, client, cut, y):
        t = types.SimpleNamespace(
            request=types.SimpleNamespace(client=client, t_cut=cut, y=y),
            t_admit=-1.0, t_retire=-1.0)
        self.pending.append(t)
        return t

    def poll(self):
        time.sleep(self.poll_s)
        self.polls += 1
        now = time.perf_counter()
        for t in self.pending:
            t.t_admit = now
        self.admitted += self.pending
        self.pending = []
        if self.polls % 3:
            return []
        done, self.admitted = self.admitted[:4], self.admitted[4:]
        for t in done:
            t.t_retire = now
        return done


def test_window_runs_from_poll_to_poll_over_whole_bunches():
    drv = BunchedDriver()
    opened = []
    stream = gen.ServeStream(TRAFFIC, 8, 11)
    t_open, t_close, window, polls = serve.closed_window(
        drv, stream, TRAFFIC, 0.05, on_open=lambda: opened.append(1))
    assert opened == [1]
    assert t_close - t_open >= 0.05
    # every poll after the opening one retired a whole bunch, all counted
    assert len(window) == sum(n for _, n in polls) == 4 * len(polls)
    assert polls[-1][0] == pytest.approx(t_close - t_open)
    assert all(t_open < t.t_retire <= t_close for t in window)


def _wave(cut, clients, at):
    return [types.SimpleNamespace(
        request=types.SimpleNamespace(client=c, t_cut=cut), t_admit=at)
        for c in clients]


def test_check_sample_takes_a_whole_wave_and_every_client():
    cuts = [200, 100, 100]
    window = (_wave(200, [0, 0, 0], 1.0) + _wave(100, [1, 1, 1], 2.0) +
              _wave(200, [0, 0, 0], 3.0) + _wave(100, [1, 2, 2], 4.0) +
              _wave(100, [1, 2], 5.0))            # partial: never the wave
    for seed in range(20):
        sample = serve.check_sample(window, cuts, 3,
                                    np.random.default_rng(seed))
        assert len(sample) == 3 + 1 + 3 + 2
        at_100, at_200 = sample[:5], sample[5:]     # cuts in order
        assert len({t.t_admit for t in at_200[:3]}) == 1
        assert len({t.t_admit for t in at_100[:3]}) == 1
        assert at_100[0].t_admit in (2.0, 4.0)
        assert [t.request.client for t in at_100[3:]] == [1, 2]
        assert all(t.t_admit != at_100[0].t_admit for t in at_100[3:])
        assert len({id(t) for t in sample}) == len(sample)


def test_check_sample_needs_each_client_outside_the_wave():
    window = _wave(100, [0, 1], 1.0)
    with pytest.raises(RuntimeError, match="client 0"):
        serve.check_sample(window, [100, 100], 2, np.random.default_rng(0))
