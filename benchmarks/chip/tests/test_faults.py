"""A run with the timed path broken underneath reads ``correct: false``.

Each test drives a whole run of a cell (set-up, window, check) at the
rehearsal size on the CPU, skipping only the look for a chip, with one
fault planted in the program:

* ``state_unchanged``: the fused step's state is never updated after
  the first dispatch (the dirty-node updates are dropped);
* ``half_batch``: every second new request of the drained bursts is
  left out;
* ``answer_altered``: the first row of every decided burst gets 1% more
  CPU than the core computed.

The same run without a fault reads ``correct: true``.  (The cells run
on one chip, so there is no exchange between chips to leave out.)
"""
import dataclasses

import numpy as np
import pytest

import run


def plant(monkeypatch, fault):
    from repro.core import allocator
    from repro.engine.kubeadaptor import KubeAdaptor

    if fault == "state_unchanged":
        flush = KubeAdaptor._flush_state

        def stale(self):
            state, _ = flush(self)
            return state, None

        monkeypatch.setattr(KubeAdaptor, "_flush_state", stale)
    elif fault == "half_batch":
        allocate = KubeAdaptor._allocate_group
        seen = [0]

        def half(self, entries, include_pending):
            # Every second new request is left out (a burst of one row
            # loses it every other time).
            keep = [e for i, e in enumerate(entries, seen[0]) if i % 2 == 0]
            seen[0] += len(entries)
            return allocate(self, keep, include_pending)

        monkeypatch.setattr(KubeAdaptor, "_allocate_group", half)
    elif fault == "answer_altered":
        wait = allocator.PendingBurst.wait

        def altered(self):
            out = wait(self)
            if out.cpu.shape[0]:
                cpu = np.array(out.cpu, copy=True)
                cpu[0] = cpu[0] * np.float32(1.01)
                out = dataclasses.replace(out, cpu=cpu)
            return out

        monkeypatch.setattr(allocator.PendingBurst, "wait", altered)


# The benchmark's cells, and the paced 5,000-node mix kept for a later
# cell (its sweep and its check are the same code).
CELLS = [("k8s-5k", "burst"), ("k8s-5k", "poisson"),
         ("aras-testbed", "constant")]


@pytest.mark.parametrize("config,mix", CELLS)
@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_reads_incorrect(monkeypatch, config, mix, fault):
    plant(monkeypatch, fault)
    result, _ = run.execute(run.mix_cell(config, mix), seed=3, seconds=1.0,
                            trace=False, tiny=True)
    assert result["correct"] is (fault is None), result["checks"]
