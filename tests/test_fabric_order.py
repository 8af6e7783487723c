"""The fabric's event loop against an explicit (time, post order) reference.

`Fabric` keeps every pending event on one binary heap of
`(time, seq, item)` entries, `seq` counting posts. The contract every
same-timestamp batching in the engine and the serving stepper rests on:
callbacks fire in ascending time, ties in the order they were posted, and a
time behind the clock is clamped to `now`. Each test here posts a stream
through `call_at` / `call_after` and compares the firing order with the
sorted reference written out or built beside it. The hypothesis twin lives
in tests/test_properties.py.
"""
import numpy as np
import pytest

from repro.core import Fabric, FabricSpec, Topology
from repro.core.fabric import FAR_WINDOW


def _fabric() -> Fabric:
    return Fabric(Topology(FabricSpec(n_nodes=2)), seed=7)


def _post(fab, fired, t, label):
    fab.call_at(t, lambda: fired.append(label))


def _times(rng, n, shape):
    span = float(rng.choice([1e-4, 1.0, 1e4])) if shape == "spans" else 1.0
    times = rng.uniform(0.0, span, size=n)
    if shape == "ties":
        # collapse most of the stream onto a handful of shared timestamps
        ties = rng.random(n) < float(rng.choice([0.5, 0.95]))
        pool = rng.uniform(0.0, span, size=max(1, n // 16))
        times[ties] = rng.choice(pool, size=int(ties.sum()))
    elif shape == "far":
        times[rng.random(n) < 0.1] = FAR_WINDOW
    return times


@pytest.mark.parametrize("shape", ["ties", "far", "spans"])
def test_seeded_stream_fires_in_time_then_post_order(shape):
    """Seeded bulk streams: heavy ties, far-future sentinels, and spans
    from 1e-4 to 1e4 virtual seconds."""
    rng = np.random.default_rng(101)
    for trial in range(40):
        times = _times(rng, int(rng.integers(1, 400)), shape)
        fab, fired = _fabric(), []
        for i, t in enumerate(times):
            _post(fab, fired, float(t), i)
        fab.run_until_idle()
        want = [i for _, i in sorted((float(t), i) for i, t in enumerate(times))]
        assert fired == want, f"trial {trial}"
        assert fab.idle


def test_ties_fire_in_post_order():
    """500 callbacks at one timestamp, labels shuffled: they fire in the
    order they were posted, not in label order."""
    labels = list(range(500))
    np.random.default_rng(7).shuffle(labels)
    fab, fired = _fabric(), []
    for label in labels:
        _post(fab, fired, 0.125, label)
    fab.run_until_idle()
    assert fired == labels
    assert fab.now == 0.125


def test_post_behind_now_is_clamped_and_ordered():
    """A time behind the clock fires at `now`, in post order with every
    other post due then, whether posted from outside or from inside a
    callback."""
    fab, fired = _fabric(), []
    fab.run_until(1.0)
    _post(fab, fired, 0.5, "a")
    _post(fab, fired, 1.0, "b")
    _post(fab, fired, 0.2, "c")
    _post(fab, fired, 1.5, "d")
    _post(fab, fired, 0.9, "e")
    _post(fab, fired, 2.0, "f")
    fab.call_at(2.0, lambda: _post(fab, fired, 1.0, "g"))
    _post(fab, fired, 2.0, "h")
    fab.run_until_idle()
    assert fired == ["a", "b", "c", "e", "d", "f", "h", "g"]
    assert fab.now == 2.0


def test_interleaved_with_run_until():
    """The fabric's access pattern: new work lands at or after `now`
    between partial drains, and each `run_until` fires exactly the due
    prefix of the reference and leaves the clock at its horizon."""
    rng = np.random.default_rng(202)
    for trial in range(30):
        fab, fired, pending, seq = _fabric(), [], [], 0
        for _ in range(int(rng.integers(10, 60))):
            for _ in range(int(rng.integers(1, 12))):
                t = fab.now + float(rng.uniform(0.0, 5e-3))
                pending.append((t, seq))
                _post(fab, fired, t, seq)
                seq += 1
            horizon = fab.now + float(rng.uniform(0.0, 4e-3))
            due = sorted(e for e in pending if e[0] <= horizon)
            pending = [e for e in pending if e[0] > horizon]
            before = len(fired)
            fab.run_until(horizon)
            assert fired[before:] == [k for _, k in due], f"trial {trial}"
            assert fab.now == horizon
        before = len(fired)
        fab.run_until_idle()
        assert fired[before:] == [k for _, k in sorted(pending)]


def test_nested_callback_lands_on_a_tie():
    """Interleaved `call_at` / `call_after` with heavy ties, and a callback
    that posts more work mid-drain onto an already occupied timestamp: the
    nested post fires after the entries posted before it at that time."""
    fab, fired = _fabric(), []
    for i, t in enumerate([0.003, 0.001, 0.002, 0.001, 0.001, 0.0025, 0.002]):
        _post(fab, fired, t, i)
    fab.call_after(0.001, lambda: fired.append("after"))
    fab.call_at(0.002, lambda: _post(fab, fired, 0.0025, "nested"))
    fab.run_until(0.01)
    assert fired == [1, 3, 4, "after", 2, 6, 5, "nested", 0]
    assert fab.now == 0.01
