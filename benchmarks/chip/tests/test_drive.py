"""The warm-up copy of a paced cell's engine decides as the engine does.

Set-up serves the window's arrivals on a deep copy of the window's
engine, so that every shape is compiled before the window.  The copy
must leave the original untouched (its device state is donated by the
fused step) and must decide exactly what the original then decides.
"""
import run
import drive
import traffic


def test_fork_decides_as_the_original():
    from repro.api import EngineConfig

    cell = run.mix_cell("k8s-5k", "poisson")
    cfile = cell["config_file"]
    engine = traffic.merge(cfile["engine"], cfile["rehearsal"]["engine"])
    mix = traffic.merge(cell["mix"], cell["mix"]["rehearsal"])
    cfg = EngineConfig.from_dict(engine).evolve(alloc_backend="pallas")
    stream = traffic.arrivals(mix, 11, 2.0)
    program = drive.to_program(stream)
    t_w = stream[int(mix["warmup"]["arrivals"]) - 1][0]
    horizon = t_w + 2.0

    driver = drive.Driver(cfg)
    episode = drive.Episode()
    eng = driver.engine(episode)
    nxt = driver.run_unpaced(eng, episode, program, 0, horizon=t_w)
    before = dict(episode.binds)

    twin_episode = drive.Episode()
    twin = driver.fork(eng, twin_episode)
    driver.run_unpaced(twin, twin_episode, program, nxt, horizon=horizon)
    assert twin_episode.binds, "the copy bound nothing"
    assert episode.binds == before, "the copy's binds reached the original"

    driver.run_unpaced(eng, episode, program, nxt, horizon=horizon)
    assert {k: episode.binds[k] for k in twin_episode.binds} \
        == twin_episode.binds
    assert set(episode.binds) == set(before) | set(twin_episode.binds)


def _rehearsal(config, mix_name):
    from repro.api import EngineConfig

    cell = run.mix_cell(config, mix_name)
    cfile = cell["config_file"]
    engine = traffic.merge(cfile["engine"],
                           cfile.get("rehearsal", {}).get("engine", {}))
    mix = traffic.merge(cell["mix"], cell["mix"]["rehearsal"])
    cfg = EngineConfig.from_dict(engine).evolve(alloc_backend="pallas")
    return cfg, mix


def test_counters_are_the_engines_deltas_paced():
    """A paced window's counters are the change in every integer field
    of the engine's metrics, and agree with the per-step counts."""
    cfg, mix = _rehearsal("k8s-5k", "poisson")
    stream = traffic.arrivals(mix, 12, 1.0)
    program = drive.to_program(stream)
    t_w = stream[int(mix["warmup"]["arrivals"]) - 1][0]
    driver = drive.Driver(cfg)
    episode = drive.Episode()
    eng = driver.engine(episode)
    nxt = driver.run_unpaced(eng, episode, program, 0, horizon=t_w)
    before = drive.counters(eng.metrics)
    assert before["num_dispatches"] > 0  # the warm-up prefix dispatched
    window = drive.Window(1.0)
    drive.paced(driver, eng, episode, program, nxt, t_w, 1.0, 1.0, window)
    after = drive.counters(eng.metrics)
    assert window.counters == {k: after[k] - before[k] for k in after}
    assert window.counters["num_dispatches"] == window.dispatches > 0
    assert window.counters["dispatched_rows"] == sum(window.dispatch_rows)
    assert window.counters["staged_bytes"] > 0
    assert window.counters["fetched_bytes"] > 0


def test_counters_sum_over_episodes():
    """Closed-loop episodes each run on a fresh engine; the window's
    counters sum what every episode's engine counted."""
    cfg, mix = _rehearsal("k8s-5k", "burst")
    stream = traffic.arrivals(mix, 12, 1.0)
    program = drive.to_program(stream)
    engines = []
    driver = drive.Driver(cfg)
    build = driver.engine

    def kept(episode):
        engines.append(build(episode))
        return engines[-1]

    driver.engine = kept
    window = drive.Window(0.5)
    drive.closed(driver, stream[-1][0], 0.5, window, program)
    assert engines and all(e.complete for e in window.episodes)
    summed = {}
    for eng in engines:
        for k, v in drive.counters(eng.metrics).items():
            summed[k] = summed.get(k, 0) + v
    assert window.counters == summed
    assert window.counters["num_dispatches"] == window.dispatches > 0
    assert window.counters["dispatched_rows"] == sum(window.dispatch_rows) \
        == traffic.pod_count(stream) * len(engines)
