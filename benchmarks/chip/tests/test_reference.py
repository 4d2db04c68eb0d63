"""The reference at K clusters against the program, and unchanged at one.

* K = 4 (4 x 64 nodes, the burst mix's rehearsal, ``sharding="off"``):
  a whole run of the cell on the CPU checks with no mismatch, and a
  program that binds a pod to the next cluster's first node is caught;
* K = 1: the reference's decisions and the bfloat16 control's readings
  on both cells' rehearsal streams are bit for bit what they were
  before the reference stated federations.
"""
import copy
import dataclasses
import hashlib
import json

import numpy as np
import pytest

import control
import run
import traffic
from reference import Reference

CLUSTERS, PER_CLUSTER = 4, 64


def federation_cell():
    cell = run.mix_cell("k8s-5k", "burst")
    cfile = copy.deepcopy(cell["config_file"])
    cfile["engine"]["cluster"].update(num_clusters=CLUSTERS, sharding="off")
    cfile["rehearsal"]["engine"]["cluster"]["num_nodes"] = \
        CLUSTERS * PER_CLUSTER
    cell["config_file"] = cfile
    return cell


def test_federation_matches_the_program():
    result, window = run.execute(federation_cell(), seed=5, seconds=1.0,
                                 trace=False, tiny=True)
    checks = {k: c["value"] for k, c in result["checks"].items()}
    assert checks["mismatches"] == 0
    assert checks["never_bound"] == 0
    assert checks["overcommits"] == 0
    assert checks["quota_gap"] <= 0.002
    assert result["correct"] is True
    # The binds reach past the first cluster.
    nodes = {d[1] for d in window.episodes[0].binds.values()}
    assert max(nodes) >= PER_CLUSTER


def test_bind_to_the_next_cluster_is_caught(monkeypatch):
    """Each engine's first pod, which worst fit puts on node 0, is bound
    to node 64, the next cluster's first node, which has room for it.
    Only the first: the device state never hears of the move, so its
    next pick of node 0 would overfill node 64."""
    import drive
    from repro.core import allocator

    wait = allocator.PendingBurst.wait
    engine = drive.Driver.engine
    first = [True]

    def fresh(self, episode):
        first[0] = True
        return engine(self, episode)

    def next_cluster(self):
        out = wait(self)
        if first[0] and out.node.shape[0] and out.node[0] == 0:
            first[0] = False
            node = np.array(out.node, copy=True)
            node[0] = PER_CLUSTER
            out = dataclasses.replace(out, node=node)
        return out

    monkeypatch.setattr(drive.Driver, "engine", fresh)
    monkeypatch.setattr(allocator.PendingBurst, "wait", next_cluster)
    result, _ = run.execute(federation_cell(), seed=5, seconds=1.0,
                            trace=False, tiny=True)
    assert result["checks"]["mismatches"]["value"] > 0
    assert result["correct"] is False


def test_clusters_must_partition_the_nodes():
    cell = federation_cell()
    engine = traffic.merge(cell["config_file"]["engine"],
                           {"cluster": {"num_nodes": 3}})
    with pytest.raises(ValueError):
        Reference(engine, [])


# Read from the one-cluster reference before it stated federations:
# (config, mix, seed) -> (sha256 of its binds, first 16 digits; the
# bfloat16 control's readings).
K1 = {
    ("k8s-5k", "burst", 1): ("7d4ea94559945035", 128, 11, 0.013157894736842105),
    ("k8s-5k", "burst", 2): ("2c32ecdfda1069b0", 128, 8, 0.5924928067549995),
    ("k8s-5k", "burst", 3): ("e69d0c736a0327de", 128, 21, 1.5788287402031447),
    ("aras-testbed", "constant", 1): ("34a03f13f6dcb27f", 78, 0,
                                      0.01510227489963678),
    ("aras-testbed", "constant", 2): ("96032e771847e49c", 78, 3,
                                      0.016979459338429915),
    ("aras-testbed", "constant", 3): ("78e9e97df3ca6d29", 78, 1,
                                      0.013548367674764673),
}


@pytest.mark.parametrize("config,mix,seed", sorted(K1))
def test_one_cluster_reads_as_before(config, mix, seed):
    cell = run.mix_cell(config, mix)
    cfile = cell["config_file"]
    engine = traffic.merge(cfile["engine"],
                           cfile.get("rehearsal", {}).get("engine", {}))
    m = traffic.merge(cell["mix"], cell["mix"]["rehearsal"])
    digest, decisions, mismatches, gap = K1[(config, mix, seed)]
    stream = traffic.arrivals(m, seed, 20.0)
    horizon, pods = control.horizon_of(m, stream, 20.0)
    binds = Reference(engine, stream).decide_all(horizon, pods)
    got = hashlib.sha256(json.dumps(sorted(
        [k, *d] for k, d in binds.items())).encode()).hexdigest()
    assert got[:16] == digest
    r = control.readings(engine, m, seed, 20.0)
    assert (r["decisions"], r["mismatches"], r["never_bound"],
            r["overcommits"]) == (decisions, mismatches, 0, 0)
    assert r["quota_gap"] == gap
