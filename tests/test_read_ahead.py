"""Read-ahead D2H: the scheduler learns which kernel outputs the host reads
back, and the lane starts their copy to the host as the kernel is
dispatched (``ReadAheadPredictor``, ``ComputationalElement.read_ahead``).

A site (declared function, call name, argument position) engages after
two host reads of its outputs with no device consumer between them, and
decays when an output is consumed or overwritten on the device before the
host touches it.
The copy is the same bytes, only started earlier; the simulator ignores
the mark.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as gr
from repro.benchsuite import kernels as K
from repro.core import make_scheduler
from repro.core.managed import ManagedValue
from repro.core.scheduler import ReadAheadPredictor

N = 256

SCALE = gr.function(jax.jit(lambda a, _o: a * 0.5 + 1.0), modes=("const", "out"),
                    name="SCALE")
OVERWRITE = gr.function(jax.jit(lambda a, _o: a - 1.0), modes=("const", "out"),
                        name="OVERWRITE")
BUMP = gr.function(jax.jit(lambda v: v + 1), modes=("inout",), name="BUMP")


def prices(seed):
    rng = np.random.default_rng(seed)
    return rng.random(N, np.float32) * np.float32(100) + np.float32(20)


def launch_and_read(sched, seed):
    """One B&S step: upload a stock, price it, read the price back."""
    x = sched.array(prices(seed), name=f"s{seed}")
    o = sched.array(shape=(N,), dtype=np.float32, name=f"c{seed}")
    e = K.BLACK_SCHOLES.with_options(scheduler=sched)(x, o)
    return e, np.asarray(o).copy()


def counts(sched):
    st = sched.stats()
    return st["d2h_ahead_issued"], st["d2h_ahead_hits"]


def test_engages_after_two_reads_and_every_read_is_served():
    with make_scheduler("parallel") as sched:
        marked = []
        for i in range(6):
            e, _ = launch_and_read(sched, i)
            marked.append(e.read_ahead)
            issued, hits = counts(sched)
            assert issued == hits == max(0, i - 1)
        assert marked == [(), ()] + [(1,)] * 4
        assert counts(sched) == (4, 4)


def test_outputs_are_bit_identical_to_an_unmarked_copy():
    with make_scheduler("parallel") as sched:
        engaged = [launch_and_read(sched, i) for i in range(5)]
    assert engaged[-1][0].read_ahead == (1,)
    for i, (_, got) in enumerate(engaged):
        with make_scheduler("parallel") as fresh:    # one read: never marked
            e, want = launch_and_read(fresh, i)
            assert e.read_ahead == () and counts(fresh) == (0, 0)
        assert got.tobytes() == want.tobytes()


def test_a_replayed_output_the_host_reads_every_time_is_read_ahead():
    """Under capture, every episode after the first is replayed from its
    plan; a replayed kernel passes the same admission stage as an eager
    one, so its output engages and is read ahead the same way."""
    with make_scheduler("parallel") as sched:
        marked, seen, got = [], [], []
        for i in range(6):
            with sched.capture("bs_step"):
                e, out = launch_and_read(sched, i)
            marked.append(e.read_ahead)
            seen.append(counts(sched))
            got.append(out)
        assert sched.stats()["plan_replays"] == 5
    assert marked == [(), ()] + [(1,)] * 4
    assert seen == [(0, 0), (0, 0), (1, 1), (2, 2), (3, 3), (4, 4)]
    for i, out in enumerate(got):
        with make_scheduler("parallel") as fresh:    # eager, never marked
            _, want = launch_and_read(fresh, i)
        assert out.tobytes() == want.tobytes()


def test_outputs_consumed_on_the_device_are_never_issued():
    """VEC: SQUARE's outputs feed REDUCE_DIFF; only its 1-element result is
    read, so only that site engages."""
    with make_scheduler("parallel") as sched:
        squares, reduces = [], []
        for it in range(4):
            x1 = sched.array(prices(it), name=f"x1_{it}")
            x2 = sched.array(prices(it + 100), name=f"x2_{it}")
            y1 = sched.array(shape=(N,), dtype=np.float32, name=f"y1_{it}")
            y2 = sched.array(shape=(N,), dtype=np.float32, name=f"y2_{it}")
            z = sched.array(shape=(1,), dtype=np.float32, name=f"z_{it}")
            squares += [K.SQUARE.with_options(scheduler=sched)(x1, y1),
                        K.SQUARE.with_options(scheduler=sched)(x2, y2)]
            reduces.append(
                K.REDUCE_DIFF.with_options(scheduler=sched)(y1, y2, z))
            np.asarray(z)
        assert all(e.read_ahead == () for e in squares)
        assert [e.read_ahead for e in reduces] == [(), (), (2,), (2,)]
        e = squares[0]
        assert sched.read_ahead.counters[(e.fn_key, e.name, 1)] == 0
        assert counts(sched) == (2, 2)


def test_an_output_consumed_each_iteration_and_read_at_the_end_stays_off():
    """HITS: DIVIDE, called as DIV_A and DIV_H, writes auth and hub; each
    iteration's pair feeds the next iteration's kernels and only the last
    pair is read, run after run."""
    with make_scheduler("parallel") as sched:
        for run in range(5):
            auth = sched.array(prices(run), name=f"auth{run}_0")
            hub = sched.array(prices(run + 100), name=f"hub{run}_0")
            for it in range(3):
                a_new = sched.array(shape=(N,), dtype=np.float32)
                h_new = sched.array(shape=(N,), dtype=np.float32)
                marked = [
                    SCALE.with_options(scheduler=sched, name="DIV_A")(
                        hub, a_new).read_ahead,
                    SCALE.with_options(scheduler=sched, name="DIV_H")(
                        auth, h_new).read_ahead]
                assert marked == [(), ()]
                auth, hub = a_new, h_new
            np.asarray(auth), np.asarray(hub)
        for name in ("DIV_A", "DIV_H"):
            assert sched.read_ahead.counters[(SCALE.fid, name, 1)] < 2
        assert counts(sched) == (0, 0)


@pytest.mark.parametrize("change", ["consumed", "overwritten"])
def test_a_pattern_that_stops_reading_decays_and_stops_issuing(change):
    with make_scheduler("parallel") as sched:
        marks = []
        for i in range(3):          # read: the site engages on the third
            x = sched.array(prices(i), name=f"x{i}")
            o = sched.array(shape=(N,), dtype=np.float32, name=f"o{i}")
            marks.append(bool(SCALE.with_options(scheduler=sched)(
                x, o).read_ahead))
            np.asarray(o)
        for i in range(3, 7):       # then its outputs stay on the device
            x = sched.array(prices(i), name=f"x{i}")
            o = sched.array(shape=(N,), dtype=np.float32, name=f"o{i}")
            marks.append(bool(SCALE.with_options(scheduler=sched)(
                x, o).read_ahead))
            if change == "consumed":
                OVERWRITE.with_options(scheduler=sched)(
                    o, sched.array(shape=(N,), dtype=np.float32))
            else:
                OVERWRITE.with_options(scheduler=sched)(x, o)
        sched.sync()
        assert marks == [False, False, True, True, True, False, False]
        assert counts(sched) == (3, 1)     # two copies made for nothing


def test_values_without_a_host_mirror_are_never_marked():
    with make_scheduler("parallel") as sched:
        v = ManagedValue(sched, jnp.zeros(4), name="state")
        for _ in range(4):
            e = BUMP.with_options(scheduler=sched)(v)
            v.get()
            assert e.read_ahead == ()
        assert counts(sched) == (0, 0)


def test_a_kernel_returning_a_host_value_is_read_as_before():
    """A marked output that is already a host array has no copy to start:
    the read gets the same value and counts no hit."""
    host_kernel = gr.function(lambda a, _o: np.asarray(a) * np.float32(2),
                              modes=("const", "out"), name="HOST")
    with make_scheduler("parallel") as sched:
        for i in range(4):
            x = sched.array(prices(i), name=f"x{i}")
            o = sched.array(shape=(N,), dtype=np.float32, name=f"o{i}")
            e = host_kernel.with_options(scheduler=sched)(x, o)
            np.testing.assert_array_equal(np.asarray(o), prices(i) * 2)
        assert e.read_ahead == (1,)
        assert counts(sched) == (2, 0)


def test_the_simulator_ignores_the_mark(monkeypatch):
    """Simulated times are the same with the predictor engaged and with it
    held off."""

    def simulate():
        with make_scheduler("parallel", simulate=True) as sched:
            for i in range(6):
                launch_and_read(sched, i)
            sched.sync()
            spans = [(s.kind, s.lane, s.t0, s.t1)
                     for s in sched.timeline.spans]
            return sched.executor.host_time, spans, counts(sched)

    engaged = simulate()
    assert engaged[2][0] == 4
    monkeypatch.setattr(ReadAheadPredictor, "ENGAGE", 99)
    held_off = simulate()
    assert held_off[2] == (0, 0)
    assert engaged[:2] == held_off[:2]
