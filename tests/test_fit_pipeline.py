"""``fit(iterator, steps_per_loop=k)`` keeps one group in flight
(``nn/_fit_ahead.py``): group n+1 is pulled, staged and launched under
loop n, and only then are loop n's losses read. What training leaves
is, bit for bit, what the same batches leave fed one group a call (the
order ``fit`` had); the step records say which groups ran ahead;
whatever raises, no group is lost and nothing stays in flight.

Toy nets with batch norm (a ``state``), dropout (the rng keys matter)
and momentum (an ``opt_state``), on the CPU, both nets throughout.
"""
import gc
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (BatchNormalization, DenseLayer,
                                          DropoutLayer, OutputLayer)
from deeplearning4j_tpu.resilience import faults

K = 3       # steps a loop


def _mln():
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(upd.Nesterovs(learning_rate=0.05))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(BatchNormalization())
            .layer(DropoutLayer(dropout=0.3))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph():
    conf = (NeuralNetConfiguration.builder().seed(11)
            .updater(upd.Nesterovs(learning_rate=0.05))
            .graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("bn", BatchNormalization(), "d")
            .add_layer("drop", DropoutLayer(dropout=0.3), "bn")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                          loss="mcxent"), "drop")
            .set_outputs("out")
            .set_input_types(**{"in": InputType.feed_forward(4)})
            .build())
    return ComputationGraph(conf).init()


NETS = {"graph": (_graph, "ComputationGraph.fit"),
        "mln": (_mln, "MultiLayerNetwork.fit")}


@pytest.fixture(params=sorted(NETS))
def kind(request):
    return request.param


def _batches(n, b=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((b, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
        out.append(DataSet(x, y))
    return out


class Log:
    """A listener that keeps what it is told, and the score."""

    def __init__(self):
        self.calls, self.epoch_ends = [], []

    def iteration_done(self, net, iteration, epoch):
        self.calls.append((iteration, epoch, net.score_))

    def on_epoch_start(self, net):
        pass

    def on_epoch_end(self, net):
        # nothing in flight: every iteration of the epoch is booked
        self.epoch_ends.append(net.iteration)


def _net(kind):
    net = NETS[kind][0]()
    net.listeners.append(Log())
    return net


def _one_group_a_call(net, batches, k=K):
    """The order ``fit`` had: no group launched before the one before
    it was read."""
    for i in range(0, len(batches), k):
        net.fit(ListDataSetIterator(batches[i:i + k]), steps_per_loop=k)
    return net


def _host(tree):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(tree)]


def _assert_same_training(a, b):
    assert a.iteration == b.iteration
    assert a.score_ == b.score_
    assert [(i, s) for i, _, s in a.listeners[0].calls] == \
        [(i, s) for i, _, s in b.listeners[0].calls]
    for name in ("params", "opt_state", "state"):
        for la, lb in zip(_host(getattr(a, name)), _host(getattr(b, name)),
                          strict=True):
            assert np.array_equal(la, lb), name


def _groups(kind, seen):
    """The step records of scanned groups written since ``seen``."""
    return [r for r in obs.trace.records()
            if r.name == NETS[kind][1] and r.counts
            and "steps" in r.counts][seen:]


def _seen(kind):
    return len(_groups(kind, 0))


def _nothing_in_flight(net):
    return all(leaf.is_ready() for leaf in jax.tree.leaves(
        (net.params, net.opt_state, net.state)))


# -- (1) same numbers ------------------------------------------------------

@pytest.mark.parametrize("n_groups", [3, 5])
def test_groups_through_one_fit_equal_one_group_a_call(kind, n_groups):
    batches = _batches(n_groups * K)
    ahead = _net(kind).fit(ListDataSetIterator(batches), steps_per_loop=K)
    ref = _one_group_a_call(_net(kind), batches)
    assert ahead.iteration == n_groups * K
    _assert_same_training(ahead, ref)


# -- (2) the records -------------------------------------------------------

def test_records_say_which_groups_ran_ahead(kind):
    net, seen = _net(kind), _seen(kind)
    net.fit(ListDataSetIterator(_batches(4 * K)), steps_per_loop=K)
    recs = _groups(kind, seen)
    assert [r.counts["ahead"] for r in recs] == [0, 1, 1, 1]
    assert [r.counts["staged_ahead"] for r in recs] == [0, 1, 1, 1]
    assert [r.counts["iteration"] for r in recs] == [0, 3, 6, 9]
    assert all(r.counts["steps"] == K and r.counts["bytes"] > 0
               for r in recs)
    for r in recs:
        assert r.phases == ("prep", "h2d", "hold", "dispatch", "flight",
                            "sync")
        assert list(r.stamps) == sorted(r.stamps)
        dispatch = r.stamps[r.phases.index("dispatch")]
        assert dispatch < r.stamps[-1]      # before its own sync's end
    # records of neighbours overlap: a group is launched before the
    # one before it is read, and read after it
    for a, b in zip(recs, recs[1:]):
        sync_a = a.stamps[a.phases.index("sync")]
        assert b.stamps[b.phases.index("dispatch")] < sync_a
        assert a.stamps[-1] <= b.stamps[b.phases.index("sync")]
    # a call of one group, as the benchmark's set-up makes: not ahead
    net.fit(ListDataSetIterator(_batches(K)), steps_per_loop=K)
    assert _groups(kind, seen)[4].counts["ahead"] == 0
    assert _nothing_in_flight(net)


def test_step_metrics_count_a_group_once(kind):
    entry = NETS[kind][1]
    steps = obs.metrics.STEPS.labels(entry=entry)
    sync = obs.metrics.SYNC_SECONDS.labels(entry=entry)
    before, waited = steps.value, sync.value
    _net(kind).fit(ListDataSetIterator(_batches(3 * K)), steps_per_loop=K)
    assert steps.value == before + 3
    assert sync.value > waited


# -- (3) something raises with a group in flight ---------------------------

class _FailsAt:
    def __init__(self, iteration):
        self.at = iteration

    def iteration_done(self, net, iteration, epoch):
        if iteration == self.at:
            raise FloatingPointError(f"listener at {iteration}")

    def on_epoch_start(self, net):
        pass

    def on_epoch_end(self, net):
        pass


class _LossesThatFail:
    """A loop's losses whose read raises, as a device fault surfaces."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("the device lost group 2")


@pytest.mark.parametrize("site", ["iterator", "step", "listener"])
def test_host_error_with_a_group_in_flight(kind, site):
    """The group on the device is read, booked and shown to the
    listeners, THEN the error raises; a second ``fit`` goes on from
    there, bit for bit."""
    batches = _batches(5 * K)
    net = _net(kind)
    if site == "iterator":
        # the pull of batch 8 raises under loop 2; batch 7 is lost
        booked, error = 2 * K, OSError

        def feed():
            yield from batches[:2 * K + 1]
            raise OSError("the reader lost its file")

        with pytest.raises(error, match="lost its file"):
            net.fit(feed(), steps_per_loop=K)
    elif site == "step":
        # the fault site of group 3 fires with loop 2 in flight
        booked, error = 2 * K, faults.InjectedFault
        with faults.active("step:error=InjectedFault:nth=3:max=1"):
            with pytest.raises(error):
                net.fit(ListDataSetIterator(batches), steps_per_loop=K)
    else:
        # a listener of group 2 raises with loop 3 launched: group 3
        # is read and booked too, its listeners called
        booked, error = 3 * K, FloatingPointError
        net.listeners.append(_FailsAt(2 * K))
        with pytest.raises(error, match=f"listener at {2 * K}"):
            net.fit(ListDataSetIterator(batches), steps_per_loop=K)
        net.listeners.pop()
    assert net.iteration == booked
    assert [i for i, _, _ in net.listeners[0].calls] == \
        list(range(1, booked + 1))
    assert _nothing_in_flight(net)
    ref = _one_group_a_call(_net(kind), batches[:booked])
    _assert_same_training(net, ref)
    net.fit(ListDataSetIterator(batches[booked:booked + 2 * K]),
            steps_per_loop=K)
    _one_group_a_call(ref, batches[booked:booked + 2 * K])
    _assert_same_training(net, ref)


def test_group_whose_read_raises_takes_its_successor_with_it(kind):
    net, seen = _net(kind), _seen(kind)
    loop = net._make_train_loop()
    calls = []

    def failing(*args):
        out = loop(*args)
        calls.append(1)
        return out[:-1] + (_LossesThatFail(),) if len(calls) == 2 else out

    net._train_loop_fn = failing
    with pytest.raises(RuntimeError, match="lost group 2"):
        net.fit(ListDataSetIterator(_batches(5 * K)), steps_per_loop=K)
    # group 1 booked; group 2's read raised with group 3 launched:
    # neither has a record, an iteration or a listener call
    assert len(calls) == 3
    assert net.iteration == K
    assert len(net.listeners[0].calls) == K
    assert len(_groups(kind, seen)) == 1


def test_drain_that_fails_rides_on_the_first_error(kind):
    net = _net(kind)
    net.listeners.append(_FailsAt(2 * K))       # group 2's listener ...
    net.listeners.append(_FailsAt(3 * K))       # ... and group 3's
    with pytest.raises(FloatingPointError,
                       match=f"listener at {2 * K}") as caught:
        net.fit(ListDataSetIterator(_batches(5 * K)), steps_per_loop=K)
    assert any(f"listener at {3 * K}" in note
               for note in caught.value.__notes__)
    assert net.iteration == 3 * K and _nothing_in_flight(net)


def test_interrupt_waits_for_no_group(kind):
    class Interrupts(_FailsAt):
        def iteration_done(self, net, iteration, epoch):
            if iteration == self.at:
                raise KeyboardInterrupt

    net = _net(kind)
    net.listeners.append(Interrupts(K + 1))     # group 2's first
    with pytest.raises(KeyboardInterrupt):
        net.fit(ListDataSetIterator(_batches(5 * K)), steps_per_loop=K)
    # group 3 was launched and is dropped unread: no listener call
    assert net.iteration == K + 1
    assert len(net.listeners[0].calls) == K + 1


# -- (4) reads_state --------------------------------------------------------

class _Snapshots(Log):
    """Copies ``net.params`` at one iteration; says so or not."""

    def __init__(self, at, says_so):
        super().__init__()
        self.at, self.says_so, self.params = at, says_so, None

    def reads_state(self, iteration):
        return self.says_so and iteration == self.at

    def iteration_done(self, net, iteration, epoch):
        super().iteration_done(net, iteration, epoch)
        if iteration == self.at:
            self.params = _host(net.params)


@pytest.mark.parametrize("says_so", [True, False])
def test_listener_that_reads_state_finds_its_own_group(kind, says_so):
    """Where a listener says it reads the state at an iteration of
    group 2, group 2 is read and shown before group 3 is launched (and
    after it is staged). One that does not say so finds the state one
    group newer: group 3 has taken ``net.params`` over."""
    batches = _batches(4 * K)
    net, seen = NETS[kind][0](), _seen(kind)
    net.listeners.append(_Snapshots(K + 2, says_so))    # inside group 2
    net.fit(ListDataSetIterator(batches), steps_per_loop=K)
    recs = _groups(kind, seen)
    ref = _one_group_a_call(NETS[kind][0](), batches[:2 * K])
    if not says_so:
        _one_group_a_call(ref, batches[2 * K:3 * K])
    for got, want in zip(net.listeners[0].params, _host(ref.params),
                         strict=True):
        assert np.array_equal(got, want)
    assert [r.counts["ahead"] for r in recs] == \
        ([0, 1, 0, 1] if says_so else [0, 1, 1, 1])
    assert [r.counts["staged_ahead"] for r in recs] == [0, 1, 1, 1]
    if says_so:
        # group 3 waited, staged, for group 2's read and listeners
        hold = recs[2].phases.index("hold")
        assert recs[2].stamps[hold + 1] >= recs[1].stamps[-1]
    # the numbers are the blocking order's either way
    _assert_same_training(net, _one_group_a_call(_net(kind), batches))


# -- (5) what drains first --------------------------------------------------

def _ahead_of(kind, seen):
    return [(r.counts["steps"], r.counts["ahead"])
            for r in _groups(kind, seen)]


def test_signature_change_drains_first(kind):
    batches = _batches(2 * K, b=16) + _batches(2 * K, b=8, seed=1)
    net, seen = _net(kind), _seen(kind)
    net.fit(ListDataSetIterator(batches), steps_per_loop=K)
    assert _ahead_of(kind, seen) == [(K, 0), (K, 1), (K, 0), (K, 1)]
    _assert_same_training(net, _one_group_a_call(_net(kind), batches))


@pytest.mark.parametrize("tail", [1, 2])
def test_short_tail_drains_first(kind, tail):
    batches = _batches(2 * K + tail)
    net, seen = _net(kind), _seen(kind)
    net.fit(ListDataSetIterator(batches), steps_per_loop=K)
    # one batch runs through the per-batch step, two as a loop of two:
    # neither is the next whole group
    assert _ahead_of(kind, seen) == \
        [(K, 0), (K, 1)] + ([(2, 0)] if tail == 2 else [])
    assert net.iteration == len(batches)
    _assert_same_training(net, _one_group_a_call(_net(kind), batches))


def test_due_numerics_step_drains_first(kind):
    batches = _batches(4 * K)
    net, seen = _net(kind), _seen(kind)
    net.monitor_numerics(every=2 * K + 1)     # due at iteration 6: group 3
    net.fit(ListDataSetIterator(batches), steps_per_loop=K)
    # group 3 runs batch by batch, its diagnostic step among them, with
    # nothing in flight; group 4 starts a pipeline anew
    assert _ahead_of(kind, seen) == [(K, 0), (K, 1), (K, 0)]
    assert [r.counts["iteration"] for r in _groups(kind, seen)] == \
        [0, K, 3 * K]
    assert net.last_numerics is not None
    assert [i for i, _, _ in net.listeners[0].calls] == \
        list(range(1, 4 * K + 1))
    ref = _net(kind)
    ref.monitor_numerics(every=2 * K + 1)
    _assert_same_training(net, _one_group_a_call(ref, batches))


def test_epoch_boundary_drains_first(kind):
    batches = _batches(2 * K)
    net, seen = _net(kind), _seen(kind)
    net.fit(ListDataSetIterator(batches), steps_per_loop=K, epochs=2)
    assert _ahead_of(kind, seen) == [(K, 0), (K, 1), (K, 0), (K, 1)]
    assert net.listeners[0].epoch_ends == [2 * K, 4 * K]
    assert [e for _, e, _ in net.listeners[0].calls] == \
        [0] * 2 * K + [1] * 2 * K
    assert net.epoch == 2


def test_single_batches_between_groups_run_alone():
    """``MultiLayerNetwork.fit`` sends a masked batch through the
    per-batch step: what is in flight is read first."""
    batches = _batches(2 * K + 1)
    masked = batches[K]
    masked.labels_mask = np.ones((16,), np.float32)
    net, seen = _net("mln"), _seen("mln")
    net.fit(ListDataSetIterator(batches), steps_per_loop=K)
    assert _ahead_of("mln", seen) == [(K, 0), (K, 0)]
    assert [i for i, _, _ in net.listeners[0].calls] == \
        list(range(1, 2 * K + 2))


def test_armed_capture_window_runs_every_group_alone(monkeypatch):
    """``devtime`` / ``commtime`` bracket a loop from its start to its
    blocking read: with a monitor installed no group runs ahead."""
    calls = []

    class Monitor:
        def on_step_start(self, iteration):
            calls.append(("start", iteration))

        def on_step_end(self, *fns):
            calls.append(("end", len(fns)))

    monkeypatch.setattr(obs.devtime, "_MONITOR", Monitor())
    net, seen = _net("mln"), _seen("mln")
    net.fit(ListDataSetIterator(_batches(3 * K)), steps_per_loop=K)
    assert _ahead_of("mln", seen) == [(K, 0)] * 3
    assert calls == [("start", 0), ("end", 1), ("start", K), ("end", 1),
                     ("start", 2 * K), ("end", 1)]
    monkeypatch.undo()
    _assert_same_training(
        net, _net("mln").fit(ListDataSetIterator(_batches(3 * K)),
                             steps_per_loop=K))


# -- (6) nothing left behind ------------------------------------------------

@pytest.mark.parametrize("raises", [False, True])
def test_fit_leaves_nothing_behind(kind, raises):
    net = _net(kind)
    net.fit(ListDataSetIterator(_batches(K)), steps_per_loop=K)   # compile
    gc_on, threads = gc.isenabled(), threading.active_count()
    attrs = set(vars(net))
    if raises:
        net.listeners.append(_FailsAt(2 * K + 1))
        with pytest.raises(FloatingPointError):
            net.fit(ListDataSetIterator(_batches(4 * K)), steps_per_loop=K)
    else:
        net.fit(ListDataSetIterator(_batches(4 * K)), steps_per_loop=K)
    assert _nothing_in_flight(net)
    jax.block_until_ready(net.params)
    assert gc.isenabled() == gc_on
    assert threading.active_count() == threads
    assert set(vars(net)) == attrs      # the flight was the call's own


# -- (7) a process that fits and exits --------------------------------------

_SCRIPT = """
import sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
import test_fit_pipeline as t
from deeplearning4j_tpu.data import ListDataSetIterator
net = t._net({kind!r})
net.fit(ListDataSetIterator(t._batches(3 * t.K)), steps_per_loop=t.K)
assert net.iteration == 3 * t.K
print("fitted", net.iteration)
"""


def test_process_fits_three_groups_and_exits(kind):
    tests = os.path.dirname(os.path.abspath(__file__))
    code = _SCRIPT.format(root=os.path.dirname(tests), tests=tests,
                          kind=kind)
    done = subprocess.run([sys.executable, "-c", code], timeout=240,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().endswith(f"fitted {3 * K}")
