"""One group in flight: what ``ComputationGraph.fit`` and
``MultiLayerNetwork.fit`` share of ``steps_per_loop=k``.

With ``k > 1`` ``fit`` keeps ONE scanned loop on the device while the
host works on the next: with loop n running, ``fit`` pulls group n+1
from the iterator, ``_fit_group`` stages it (the copies land under the
loop) and launches loop n+1 on loop n's output arrays (donated
futures: the runtime queues it behind loop n), and only THEN are loop
n's losses read, its ``k`` iterations booked and its listeners called.
The device goes from one loop into the next; the staging, the launch,
the read-back and the listeners run under a loop. Only a call's (and
an epoch's) first group, and a group after a drain, finds the device
idle.

:class:`Flight` is that pipeline's state: an object a ``fit`` call
makes for itself and hands to its helpers, never more than two groups
long (the one just launched behind the one about to be read), empty
when ``fit`` returns or raises. Nothing of it outlives the call: no
thread, no attribute of the net, no state of the process.
"""
import sys
from typing import Any, NamedTuple

import jax
import numpy as np

from deeplearning4j_tpu import obs


class Group(NamedTuple):
    """A group whose loop is launched and whose losses are not read."""
    losses: Any     # the loop's losses, one a step, still on the device
    staged: Any     # the arrays the loop reads, as they were staged
    key: Any        # what the next group must equal to be launched
    #                 behind this one: its length and signature
    stamps: tuple   # start, staging, its end, launch, its end
    counts: dict    # the step record's counts (``steps``, ``iteration``)


class Flight:
    """One ``fit`` call's pipeline: the batches it has pulled and not
    yet launched (``pending``: the group being gathered) and the
    groups it has launched and not yet read (``groups``, oldest
    first). ``entry`` names the net's step records, ``cause`` is the id
    they carry."""

    #: a group's step record, each phase what the thread did FOR THAT
    #: GROUP, so records of neighbouring groups overlap in time:
    #: ``prep`` what came before its staging (the wait for the loop in
    #: flight to have its own inputs), ``h2d`` its staging, ``hold``
    #: the read of the group before where that had to come first (a
    #: listener reads the state that group left), ``dispatch`` its
    #: launch, ``flight`` what the thread did with it launched and
    #: unread (the read and the listeners of the group before, the
    #: pull, staging and launch of the group after), ``sync`` the
    #: blocking read of its losses
    PHASES = ("prep", "h2d", "hold", "dispatch", "flight", "sync")

    def __init__(self, net, entry: str, cause=None):
        self.net, self.entry, self.cause = net, entry, cause
        self.pending, self.groups = [], []
        self._read_end = 0.0    # when the last read returned

    def __len__(self):
        return len(self.groups)

    def steps(self) -> int:
        """Steps launched and not yet booked: a group launched now
        starts at ``net.iteration`` plus these."""
        return sum(g.counts["steps"] for g in self.groups)

    def takes(self, key) -> bool:
        """Whether a group of this length and signature may be launched
        behind what is in flight: the same program once more."""
        return not self.groups or self.groups[-1].key == key

    def wait_staged(self) -> None:
        """Wait until the loop in flight has its own inputs. One
        group's bytes cross at a time, and every group's stamps follow
        the start of the loop before it. Returns at once but under a
        call's first loop, whose bytes are still on their way when the
        next group is pulled."""
        if self.groups:
            jax.block_until_ready(self.groups[-1].staged)

    def reads_state(self) -> bool:
        """Whether a listener says that it reads the net's state as
        the group in flight leaves it (``TrainingListener.reads_state``
        at one of the group's iterations: a checkpoint, an evaluation).
        Such a group is read, and its listeners called, before the
        next one takes ``net.params`` over."""
        if not self.groups:
            return False
        counts = self.groups[-1].counts
        told = range(counts["iteration"] + 1,
                     counts["iteration"] + counts["steps"] + 1)
        for l in self.net.listeners:
            reads = getattr(l, "reads_state", None)
            if reads is not None and any(reads(i) for i in told):
                return True
        return False

    def read(self) -> None:
        """Block on the oldest group's losses, write its step record,
        book its iterations and call its listeners. A group whose READ
        raises takes the one launched on its outputs with it, unread."""
        net, g = self.net, self.groups.pop(0)
        tr = obs.now()
        try:
            losses = np.asarray(g.losses)   # one host transfer a group
        except BaseException:
            self.groups.clear()
            raise
        t3 = obs.now()
        start, t0, t1 = g.stamps[:3]
        # the wall this group ADDED: from its own start, or from the
        # read before it where it was staged and launched under that
        obs.metrics.observe_step(self.entry,
                                 t3 - max(start, self._read_end),
                                 t1 - t0, t3 - tr)
        obs.trace.record_phases(self.entry, g.stamps + (tr, t3),
                                self.PHASES, self.cause, g.counts)
        self._read_end = t3
        tl0 = obs.now()
        for loss in losses:
            net.score_ = float(loss)
            net.iteration += 1
            for l in net.listeners:
                l.iteration_done(net, net.iteration, net.epoch)
        if net._numerics is not None:
            net._numerics.note_score(net.score_)
        if net.listeners:
            obs.record(self.entry + "/listeners", tl0, obs.now(),
                       self.cause)

    def drain(self) -> None:
        """Read everything in flight, in order: before anything that
        is not the next whole group, and at an epoch's end."""
        while self.groups:
            self.read()

    def settle(self) -> None:
        """``fit`` is about to raise the error being handled. If the
        host's side raised it (the iterator, the staging, a fault
        site, a listener, a preemption's ``Preempted``), a sound group
        may be on the device whose update ``net.params`` already
        holds: it is read, booked and shown to the listeners first, so
        params, iteration and the listeners' view agree on every exit;
        what that raises in its turn rides on the error as a note. An
        interrupt waits for no group: the flight is dropped unread."""
        from deeplearning4j_tpu.resilience.policy import Preempted
        error = sys.exception()
        if not isinstance(error, (Exception, Preempted)):
            self.groups.clear()
            return
        try:
            self.drain()
        except (Exception, Preempted) as drained:
            self.groups.clear()
            error.add_note("reading the group in flight raised "
                           f"{drained!r}")
