"""The reader of ``state_build_ms.tput`` on a small trace with a known
answer, and the federation cell's rehearsal against the reference."""
import pytest

import run
import tracing
from run import reader
from test_reducer import HOST, US, context, nested


def with_state_build():
    """``nested()`` with the dispatch building the device state:
    ``alloc.state`` at 31-33 us, inside ``engine.stage``."""
    return nested() + [(HOST, "python", "alloc.state", 31 * US, 2 * US,
                        {"bytes": 8704, "nodes": 500})]


def test_state_build_reader():
    """Self time of ``alloc.state`` per build, which ``engine.stage``'s
    self time no longer holds."""
    ctx = context(tracing.Reduction(with_state_build()), dispatches=1,
                  state_builds=1)
    assert reader("state_build_ms.tput")(ctx) == pytest.approx(2e-3)
    assert reader("stage_host_ms.lat")(ctx) == pytest.approx(4e-3)


@pytest.mark.parametrize("counters", [{}, {"state_builds": 0},
                                      {"state_builds": 2}],
                         ids=["no_counter", "none_counted", "two_counted"])
def test_state_build_reader_reads_nothing_on_a_count_mismatch(counters):
    """A program without the counter, or a count that is not the
    number of spans, reads nothing."""
    ctx = context(tracing.Reduction(with_state_build()), dispatches=1,
                  **counters)
    assert reader("state_build_ms.tput")(ctx) is None


def test_federation_cell_matches_the_program():
    """``k8s-fed100x5k.burst``'s rehearsal, 100 clusters of 16 nodes: a
    whole run on the CPU checks with no mismatch, and its 128 pods land
    on clusters 0-7, so the binds reach past the first cluster."""
    cell = run.mix_cell("k8s-fed100x5k", "burst")
    cluster = cell["config_file"]["rehearsal"]["engine"]["cluster"]
    per_cluster = cluster["num_nodes"] // 100
    assert per_cluster == 16
    result, window = run.execute(cell, seed=5, seconds=1.0, trace=False,
                                 tiny=True)
    checks = {k: c["value"] for k, c in result["checks"].items()}
    assert checks["mismatches"] == 0
    assert checks["never_bound"] == 0
    assert checks["overcommits"] == 0
    assert checks["quota_gap"] <= 0.002
    assert result["correct"] is True
    nodes = {d[1] for d in window.episodes[0].binds.values()}
    assert max(nodes) >= per_cluster
